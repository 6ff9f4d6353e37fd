"""Seconds inside one of the program's own spans (`obs.span(name)`),
from its always-on totals `xgbtpu_span_seconds_total{span}`, read
in-process after the run: `{"span": "ingest.bin"}`.  Needs no profiler,
so it reads work done before one starts.

The totals cover the whole process up to the reading, not the harness's
interval: whatever opens the span later is in them too.  In the cell
that is the label and weight puts, two `ingest.upload` spans of a few
milliseconds together that the program makes in its first training
call, after `ingest_s` has closed.  So the notes get
`<family>_span_counts`, the exits of each span of the family
(`xgbtpu_span_total{span}`), to be held against what the harness's
ingest alone opens, and, with `"within": "ingest_s"`,
`ingest_unspanned_s`: that harness span less every program span of the
same family (`ingest.*`), late ones included.  `None` where the program
keeps no such total (a version without spans) or never opened the span."""


def totals():
    """({span: seconds}, {span: exits}) of the program, or None where
    it has none."""
    try:
        from xgboost_tpu.obs.metrics import span_totals
    except ImportError:
        return None
    return span_totals().seconds.values(), span_totals().count.values()


def read(ctx, *, span, within=None):
    found = totals()
    if not found or span not in found[0]:
        return None
    secs, exits = found
    family = span.split(".", 1)[0]
    ctx["notes"][f"{family}_span_counts"] = {
        k: int(v) for k, v in sorted(exits.items())
        if k.split(".", 1)[0] == family}
    if within is not None and ctx["spans"].get(within) is not None:
        spanned = sum(v for k, v in secs.items()
                      if k.split(".", 1)[0] == family)
        ctx["notes"][f"{family}_unspanned_s"] = ctx["spans"][within] - spanned
    return secs[span]
