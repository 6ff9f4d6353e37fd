"""Device idle time of the traced window: `per` = "round" gives
milliseconds a round, "window" the share of the window in percent."""

import tracered


def read(ctx, *, per):
    tr = ctx.get("trace")
    if tr is None:
        return None
    window = tracered.window_s(tr)
    idle = window - tracered.busy_s(tr)
    if per == "window":
        return 100.0 * idle / window
    rounds = ctx["counts"].get("rounds_in_window")
    return 1e3 * idle / rounds if rounds else None
