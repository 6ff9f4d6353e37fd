"""`program_counter_per_round`: a counter the program keeps over the
window's rounds, or `None` where the program has no such counter.

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from readers import program_counter_per_round  # noqa: E402

ARGS = {"group": "training_metrics", "counter": "wait_excess_seconds",
        "scale": 1000}


def test_reads_the_counter_over_the_rounds():
    from xgboost_tpu.obs import training_metrics
    c = training_metrics().wait_excess_seconds
    c.inc(0.5)
    ctx = {"counts": {"rounds_in_window": 150}}
    assert program_counter_per_round.read(ctx, **ARGS) == pytest.approx(
        c.value * 1000 / 150)


@pytest.mark.parametrize("args", [
    dict(ARGS, counter="no_such_counter"),
    dict(ARGS, group="no_such_group")])
def test_no_counter_reads_none(args):
    ctx = {"counts": {"rounds_in_window": 150}}
    assert program_counter_per_round.read(ctx, **args) is None
