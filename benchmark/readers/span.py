"""A harness span, in seconds: `{"name": "ingest_s"}`."""


def read(ctx, *, name):
    return ctx["spans"].get(name)
