"""The rest of a run, past the look for a chip, with the timed path
broken underneath: `correct` has to come out false for every fault a
one-chip training cell can have, and true for the sound path.

    python3 -m pytest benchmark/tests -q        (CPU, about a minute)
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run_fault(fault: str, cell: str = "tiny.train_logloss") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "fault_run.py"),
                        fault, cell], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny.train_logloss", "tiny.train_auc"])
def test_sound_run_is_correct(cell):
    r = run_fault("none", cell)
    assert r["correct"] is True, r["compared"]
    assert r["metrics"] == {}           # a rehearsal writes no device metric


@pytest.mark.parametrize("fault,caught_by", [
    ("stale_state", "eval_vs_trees"),
    ("half_batch", "cover_nodes"),
    ("leaf_altered", "grad_nodes"),
    ("eval_altered", "eval_vs_trees"),
])
def test_fault_is_not_correct(fault, caught_by):
    r = run_fault(fault)
    assert r["correct"] is False
    c = r["compared"][caught_by]
    assert not c["value"] <= c["limit"], (caught_by, c)
