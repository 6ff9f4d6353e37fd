"""A count the harness took: `{"name": "compiles_in_window"}`."""


def read(ctx, *, name):
    return ctx["counts"].get(name)
