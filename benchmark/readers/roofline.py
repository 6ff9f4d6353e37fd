"""Least time for the required work (a function of `required.py`, from
the cell's shapes) over the time it took, in percent.  `work` is
"tree_histograms" or "boosting_round"; `over` is "kernels" (device time
of the Pallas calls inside `inside`, per round) or "wall" (the window's
wall time per round).  Says which peak bounds it in the notes."""

import required
import tracered


def read(ctx, *, work, over, inside=""):
    sh, rounds = ctx["shape"], ctx["counts"].get("rounds_in_window")
    if not rounds:
        return None
    if work == "tree_histograms":
        need = required.tree_histograms(sh["N"], sh["F"], sh["B"], sh["depth"])
    elif work == "boosting_round":
        need = required.boosting_round(sh["N"], sh["F"], sh["B"], sh["depth"],
                                       sh["n_held"])
    else:
        raise ValueError(f"work={work!r}")
    least, bound = required.least_seconds(need, ctx["peaks"])
    if over == "kernels":
        tr = ctx.get("trace")
        if tr is None:
            return None
        took = tracered.op_seconds(tr, inside=inside, kernels=True) / rounds
    elif over == "wall":
        took = ctx["spans"]["window_s"] / rounds
    else:
        raise ValueError(f"over={over!r}")
    if took <= 0:
        return None
    ctx["notes"][f"{ctx['metric']}_bound"] = bound
    return 100.0 * least / took
