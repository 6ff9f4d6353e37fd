"""The span and scope readers on the recorded traces (`selfcheck_spans.py`
has the hand-read expectations), and their silence where the program has
no span, scope or total to read: `None`, never 0.

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import selfcheck_spans  # noqa: E402
import tracered  # noqa: E402
from readers import program_span, trace_scope_time, trace_span_idle  # noqa: E402

SPANS = ["train.launch", "train.wait", "train.absorb", "train.eval"]
ALIGN = {"span": "train.wait", "module": "jit__scan_rounds"}


def test_selfcheck_on_the_recorded_trace():
    assert selfcheck_spans.main() == 0


@pytest.fixture(scope="module")
def old_ctx():
    """The trace recorded before the program had spans or scopes."""
    return {"trace": tracered.load(selfcheck_spans.OLD_TRACE),
            "scope_map": {}, "counts": {"rounds_in_window": 2},
            "spans": {"ingest_s": 1.0}, "notes": {}}


@pytest.mark.parametrize("span", SPANS + [None])
def test_no_span_in_the_trace_reads_none(old_ctx, span):
    assert trace_span_idle.read(old_ctx, span=span, spans=SPANS) is None
    assert trace_span_idle.read(old_ctx, span=span, spans=SPANS, align=ALIGN,
                                outer=["train.segment"]) is None
    assert "span_clock_offset_ms" not in old_ctx["notes"]
    assert "unspanned_gap_ms_by_span" not in old_ctx["notes"]


def test_the_clock_offset_is_the_least_wait_end_less_scan_end():
    tr = tracered.load(selfcheck_spans.TRACE)
    assert trace_span_idle.clock_offset(tr, **ALIGN) == pytest.approx(
        1.694983e-3, rel=1e-6)
    assert trace_span_idle.clock_offset(
        tr, "train.wait", "no_such_module") is None
    assert trace_span_idle.clock_offset(
        tr, "train.no_such_span", "jit__scan_rounds") is None


@pytest.mark.parametrize("span,unaligned", [
    ("train.launch", 0.5880395), ("train.wait", 1.765164),
    ("train.absorb", 2.445252), ("train.eval", 2.05433), (None, 0.333613)])
def test_nothing_to_align_on_reads_the_spans_as_stamped(span, unaligned):
    """Hand-read as the others, before the alignment was there."""
    ctx = {"trace": tracered.load(selfcheck_spans.TRACE), "notes": {},
           "counts": {"rounds_in_window": 2}}
    got = trace_span_idle.read(
        ctx, span=span, spans=SPANS,
        align={"span": "train.wait", "module": "no_such_module"})
    assert got == pytest.approx(unaligned, rel=1e-5)
    assert "span_clock_offset_ms" not in ctx["notes"]


@pytest.mark.parametrize("scope", ["grow.operand", "grow.split",
                                   "grow.route", "round.eval"])
def test_no_scope_in_the_module_reads_none(old_ctx, scope):
    assert trace_scope_time.read(old_ctx, inside=selfcheck_spans.SCAN,
                                 scope=scope, kernels=False) is None
    assert "xla_ms_by_scope" not in old_ctx["notes"]


def test_a_span_never_opened_reads_none(old_ctx):
    assert program_span.read(old_ctx, span="ingest.no_such_phase",
                             within="ingest_s") is None
    assert "ingest_unspanned_s" not in old_ctx["notes"]


def test_a_span_total_is_read_with_the_exits_of_its_family():
    from xgboost_tpu.obs import span
    for _ in range(2):
        with span("selftest.phase_a"):
            pass
    with span("selftest.phase_b"):
        pass
    ctx = {"spans": {"selftest_s": 1.0}, "notes": {}}
    a = program_span.read(ctx, span="selftest.phase_a", within="selftest_s")
    b = program_span.read(ctx, span="selftest.phase_b")
    assert 0 < a < 1 and 0 < b < 1
    assert ctx["notes"]["selftest_span_counts"] == {
        "selftest.phase_a": 2, "selftest.phase_b": 1}
    assert ctx["notes"]["selftest_unspanned_s"] == pytest.approx(1.0 - a - b)


def test_no_trace_reads_none():
    ctx = {"trace": None, "counts": {"rounds_in_window": 2}, "notes": {}}
    assert trace_span_idle.read(ctx, span="train.wait", spans=SPANS) is None
    assert trace_scope_time.read(ctx, inside=selfcheck_spans.SCAN,
                                 scope="grow.split") is None


def test_innermost_scope_of_an_op_name():
    f = trace_scope_time.innermost
    assert f("jit(_scan_rounds_impl)/while/body/grow.split/jit(cumsum)/add") \
        == "grow.split"
    assert f("jit(f)/while/body/closed_call/vmap(jit(grow_tree))/grow.hist/"
             "hist_level_trees/pallas_call") == "grow.hist"
    assert f("jit(f)/round.eval/grow.route/select_n") == "grow.route"
    assert f("jit(f)/while/body/add") == "" and f(None) == ""
