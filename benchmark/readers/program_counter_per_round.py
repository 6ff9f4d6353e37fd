"""A counter the program keeps, per round of the window, read in-process
after the run: `{"group": "training_metrics", "counter":
"wait_excess_seconds", "scale": 1000}` is
`xgboost_tpu.obs.training_metrics().wait_excess_seconds.value` x 1000
over `rounds_in_window`.  The counter is the process's: set-up's calls
are in it too.  `None` where the program has no such group or counter
(a version without it)."""


def read(ctx, *, group, counter, scale=1.0):
    try:
        import xgboost_tpu.obs as obs
        value = float(getattr(getattr(obs, group)(), counter).value)
    except (ImportError, AttributeError):
        return None
    return value * scale / ctx["counts"]["rounds_in_window"]
