"""A gauge the program keeps, read in-process after the run:
`{"group": "training_metrics", "gauge": "hist_row_chunks"}` is
`xgboost_tpu.obs.training_metrics().hist_row_chunks.value`.  `None`
where the program has no such group or gauge (a version without it)."""


def read(ctx, *, group, gauge):
    try:
        import xgboost_tpu.obs as obs
        return float(getattr(getattr(obs, group)(), gauge).value)
    except (ImportError, AttributeError):
        return None
