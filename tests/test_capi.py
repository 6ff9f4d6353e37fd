"""C ABI (native/xgtpu_capi.c + xgboost_tpu/capi_bridge.py).

Builds the shared library and a pure-C driver program, then runs the
driver as a REAL non-Python host: train agaricus through the C API,
eval, predict, save/load round-trip, dump.  The reference's analogous
surface is wrapper/xgboost_wrapper.cpp:113-353.
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
TRAIN = "/root/reference/demo/data/agaricus.txt.train"
TEST = "/root/reference/demo/data/agaricus.txt.test"

pytestmark = pytest.mark.skipif(
    shutil.which("gcc") is None or shutil.which("make") is None,
    reason="no C toolchain")


@pytest.fixture(scope="module")
def capi_lib():
    r = subprocess.run(["make", "-C", NATIVE, "capi"], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return os.path.join(NATIVE, "libxgboost_tpu.so")


@pytest.fixture(scope="module")
def demo_bin(capi_lib, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("capi") / "capi_demo")
    r = subprocess.run(
        ["gcc", "-O2", "-o", out, os.path.join(REPO, "tests", "capi_demo.c"),
         f"-I{NATIVE}", f"-L{NATIVE}", "-lxgboost_tpu",
         f"-Wl,-rpath,{NATIVE}"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return out


@pytest.mark.skipif(not os.path.exists(TRAIN), reason="no agaricus data")
def test_c_host_end_to_end(demo_bin, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the embedded Python must train on the backend the parity
    # reference below ran on (the TPU default is int8-quantized
    # histograms — fine for training, not for a 5e-5 equality assert)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([demo_bin, TRAIN, TEST, str(tmp_path / "m.model")],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    out = r.stdout
    assert "C-ABI-OK" in out
    assert "rows train=6513 test=1611" in out
    # the exact round-0 error of the reference demo config
    assert "train-error:0.014433" in out
    assert "roundtrip=identical" in out
    assert "dump trees=2 first_node_ok=1" in out
    # predictions parity with the Python API on the same config
    import xgboost_tpu as xgb
    dtrain = xgb.DMatrix(TRAIN)
    dtest = xgb.DMatrix(TEST, num_col=dtrain.num_col)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 1.0}, dtrain, 2, verbose_eval=False)
    want = float(np.asarray(bst.predict(dtest))[0])
    got = float(out.split("pred0=")[1].split()[0])
    # the C driver runs in its own process (different XLA flag set than
    # the 8-virtual-device conftest here), so float summation order may
    # differ in the last bits; %g printing adds ~1e-6 quantization
    assert abs(got - want) < 5e-5
