"""Rounds per executed module named `module` in the traced window."""

import tracered


def read(ctx, *, module):
    tr, rounds = ctx.get("trace"), ctx["counts"].get("rounds_in_window")
    if tr is None or not rounds:
        return None
    n = len(tracered.module_events(tr, module))
    return rounds / n if n else None
