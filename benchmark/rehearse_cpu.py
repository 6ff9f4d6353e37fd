"""CPU rehearsal of `run.py`'s phases at a tiny size: control flow,
files found by name, the reference and the comparison, the shape of the
result line.  It prints the platform, writes no device metric (the
`metrics` of its result are empty), and can never stand for a chip run:
`run.py` itself never falls back.

    python3 benchmark/rehearse_cpu.py [--workload tiny.train_logloss] [--trace 1]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import json  # noqa: E402

import run  # noqa: E402

CELLS = os.path.join(HERE, "tests", "cells")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--workload" not in argv:
        argv += ["--workload", "tiny.train_logloss"]
    if "--seed" not in argv:
        argv += ["--seed", "2147483659"]
    if "--seconds" not in argv:
        argv += ["--seconds", "2"]
    args = run.parse(argv)
    import jax
    print(f"[rehearsal] platform={jax.devices()[0].platform} "
          f"(no number below is a device metric)", file=sys.stderr)
    result = run.run_cell(args, rehearse=True, bench_dir=CELLS, root=CELLS)
    assert result["metrics"] == {}, "a rehearsal writes no device metric"
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
