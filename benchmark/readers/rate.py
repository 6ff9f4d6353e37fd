"""A count over a harness span: `{"count": "rows_ingested", "span": "ingest_s"}`."""


def read(ctx, *, count, span):
    n, s = ctx["counts"].get(count), ctx["spans"].get(span)
    return None if n is None or not s else n / s
