"""The control of `correct`: the reference put in the program's place,
computed in the nearest precision below the int8 the configurations
state (gradients in 7 steps, int4), has to come out as not correct; at
int8 it has to pass.  Test size on the CPU; the cell-size readings on
the chip are in PERF.md.

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import calibrate  # noqa: E402

CELLS = os.path.join(HERE, "cells")


@pytest.mark.parametrize("cell", ["tiny.train_logloss", "tiny.train_auc"])
@pytest.mark.parametrize("seed", [11, 2147483659, 4294967311])
def test_control_fails_and_int8_passes(cell, seed):
    r = calibrate.readings(cell, seed, bench_dir=CELLS, root=CELLS)
    assert r["int8_correct"], r["int8"]
    assert not r["int4_control_correct"], r["int4_control"]
    assert not r["half_fault_correct"], r["half_fault"]
    assert not r["leaf_fault_correct"], r["leaf_fault"]


def test_number_not_taken_is_not_correct():
    """Bin ids that cannot be read off the booster fail `bins_mismatch`
    instead of dropping it; so does any limit on a number nobody took."""
    import compare
    import run
    cell = "tiny.train_logloss"
    _, _, spec, cfg = run.find_cell(cell, CELLS, CELLS)
    gen = calibrate.importlib.import_module(f"datagen.{cfg['generator']}")
    data = gen.generate(11, cfg["n_train"], cfg["n_held"], cfg["features"],
                        **cfg.get("generator_args", {}))
    side = compare.reference_side(data, cfg)
    fit = compare.reference_fit(side, data, cfg)
    sound = compare.stand_in(fit, side["cuts"], side["bins_train"])
    assert compare.is_correct(
        compare.compare(sound, side, fit, data, cfg, spec["limits"]))
    c = compare.compare(sound._replace(bins_train=None), side, fit, data, cfg,
                        spec["limits"])
    assert c["bins_mismatch"]["value"] == 1e300 and not compare.is_correct(c)
    c = compare.compare(sound, side, fit, data, cfg,
                        dict(spec["limits"], no_such_number=1.0))
    assert c["no_such_number"]["value"] == 1e300 and not compare.is_correct(c)
