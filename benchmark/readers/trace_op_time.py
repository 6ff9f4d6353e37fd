"""Device milliseconds per round of the ops that ran inside the modules
named `inside`: `kernels` true = the Pallas custom calls, false = every
other op, absent = all."""

import tracered


def read(ctx, *, inside, kernels=None):
    tr, rounds = ctx.get("trace"), ctx["counts"].get("rounds_in_window")
    if tr is None or not rounds:
        return None
    s = tracered.op_seconds(tr, inside=inside, kernels=kernels)
    return 1e3 * s / rounds if s > 0 else None
