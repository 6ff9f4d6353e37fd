"""Gang-batched tenant lanes (xgboost_tpu.pipeline.lanes, PIPELINE.md
"Gang-batched lanes").

Acceptance criteria covered here:
(a) BIT-identity: a stacked lane's published model bytes equal its solo
    host-loop run's, byte for byte — including pad lanes (N=3 in a
    width-4 stack), N=64, mixed shape buckets, and the steady-bucket
    carry fast path;
(b) dispatch economics: stacked segment dispatches per cycle are
    INDEPENDENT of lane count within a bucket (the tentpole claim), and
    the pad/stacked accounting matches the bucket arithmetic;
(c) isolation: a gate-failing or crashing lane never poisons its
    neighbors' bytes or status;
(d) the ``XGBTPU_LANE_STACK=0`` kill switch routes through the host
    loop (zero stacked dispatches) and still produces the same bytes;
(e) steady-state compile budget: re-running an already-warm bucket
    shape compiles NOTHING (recompile_guard, ANALYSIS.md XGT001).
"""

import os
import threading

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs import lane_metrics
from xgboost_tpu.pipeline import (DataSource, SyntheticDataSource,
                                  run_tenant_lanes)
from xgboost_tpu.pipeline.lanes import LaneGang, _Arrival, _bucket_of

PARAMS = {"objective": "binary:logistic", "max_depth": 2, "eta": 0.3,
          "silent": 1}


def lane_kwargs(tmp_path, mode, name, seed, cycles=2, rounds=2,
                n_features=4, params=None, **kw):
    d = tmp_path / mode / name
    base = {
        "publish_path": str(d / "model.bin"),
        "workdir": str(d / "work"),
        "source": SyntheticDataSource(n_rows=64, n_features=n_features,
                                      seed=seed),
        "rounds_per_cycle": rounds, "cycles": cycles,
        "params": dict(params or PARAMS),
    }
    base.update(kw)
    return base


def make_lanes(tmp_path, mode, n, **kw):
    return {f"tenant{i:03d}": lane_kwargs(tmp_path, mode,
                                          f"tenant{i:03d}", 100 + i,
                                          **kw)
            for i in range(n)}


def model_bytes(kwargs):
    with open(kwargs["publish_path"], "rb") as f:
        return f.read()


def lane_counts():
    lm = lane_metrics()
    return {"dispatches": lm.dispatches.value,
            "stacked": lm.stacked.value, "padded": lm.padded.value,
            "restacks": lm.restacks.value}


def counts_delta(before):
    after = lane_counts()
    return {k: after[k] - before[k] for k in before}


def assert_all_ok(results):
    for name, r in results.items():
        assert r["status"] == "ok", (name, r)


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


# ------------------------------------------------------------- bucket key
def test_bucket_of_groups_by_shape_and_pads_rows():
    import jax.numpy as jnp
    from types import SimpleNamespace

    def spec(n_rows=100, n_features=4, w=7, subsample=1.0, K=1):
        return SimpleNamespace(
            n_rows=n_rows, n_features=n_features, subsample=subsample,
            binned=jnp.zeros((n_rows, n_features), jnp.int8),
            cut_values=jnp.zeros((n_features, w), jnp.float32),
            K=K, npar=1, n_rounds=2, seg_k=64, cfg="cfg",
            split_finder=None, grad_fn="g", pred_chunk=0)

    # rows pad to a shared power-of-two bucket when subsample == 1
    assert _bucket_of(spec(n_rows=65)) == _bucket_of(spec(n_rows=100))
    assert _bucket_of(spec(n_rows=64)) != _bucket_of(spec(n_rows=100))
    # subsample < 1 draws are N-shaped: exact rows only
    assert (_bucket_of(spec(n_rows=65, subsample=0.5))
            != _bucket_of(spec(n_rows=100, subsample=0.5)))
    assert (_bucket_of(spec(n_rows=100, subsample=0.5))
            == _bucket_of(spec(n_rows=100, subsample=0.5)))
    # cut width pads to a power of two (floor 8); features never pad
    assert _bucket_of(spec(w=5)) == _bucket_of(spec(w=8))
    assert _bucket_of(spec(n_features=5)) != _bucket_of(spec())
    assert _bucket_of(spec(K=2)) != _bucket_of(spec())


# ----------------------------------------------------------- bit-identity
@pytest.mark.parametrize("n", [2, 3, 8])
def test_stacked_bit_identity(tmp_path, n):
    """Stacked bytes == solo bytes per tenant; dispatch count per cycle
    is independent of lane count; pad accounting matches pow2 width."""
    cycles = 2
    stacked = make_lanes(tmp_path, "stacked", n, cycles=cycles)
    before = lane_counts()
    res_s = run_tenant_lanes(stacked, quiet=True, stacked=True,
                             window_sec=1.0)
    delta = counts_delta(before)
    solo = make_lanes(tmp_path, "solo", n, cycles=cycles)
    res_h = run_tenant_lanes(solo, quiet=True, stacked=False)
    assert_all_ok(res_s)
    assert_all_ok(res_h)
    for name in stacked:
        assert model_bytes(stacked[name]) == model_bytes(solo[name]), \
            f"{name}: stacked bytes != solo bytes"
    # ONE stacked dispatch per cycle regardless of n (tentpole claim)
    assert delta["dispatches"] == cycles
    assert delta["stacked"] == n * cycles
    assert delta["padded"] == (_pow2(n) - n) * cycles


def test_stacked_bit_identity_n64(tmp_path):
    cycles = 1
    stacked = make_lanes(tmp_path, "stacked", 64, cycles=cycles)
    before = lane_counts()
    res_s = run_tenant_lanes(stacked, quiet=True, stacked=True,
                             window_sec=2.0)
    delta = counts_delta(before)
    solo = make_lanes(tmp_path, "solo", 64, cycles=cycles)
    res_h = run_tenant_lanes(solo, quiet=True, stacked=False)
    assert_all_ok(res_s)
    assert_all_ok(res_h)
    mismatched = [name for name in stacked
                  if model_bytes(stacked[name]) != model_bytes(solo[name])]
    assert not mismatched, f"bytes diverged for {mismatched}"
    assert delta["dispatches"] == cycles  # width-64, still one per cycle
    assert delta["stacked"] == 64 * cycles
    assert delta["padded"] == 0


def test_mixed_shape_buckets(tmp_path):
    """Lanes with different feature counts form separate buckets that
    dispatch independently — and stay bit-identical to solo."""
    lanes_s = make_lanes(tmp_path, "stacked", 2, cycles=1)
    lanes_s.update({f"wide{i}": lane_kwargs(tmp_path, "stacked",
                                            f"wide{i}", 200 + i,
                                            cycles=1, n_features=7)
                    for i in range(2)})
    before = lane_counts()
    res_s = run_tenant_lanes(lanes_s, quiet=True, stacked=True,
                             window_sec=1.0)
    delta = counts_delta(before)
    lanes_h = make_lanes(tmp_path, "solo", 2, cycles=1)
    lanes_h.update({f"wide{i}": lane_kwargs(tmp_path, "solo",
                                            f"wide{i}", 200 + i,
                                            cycles=1, n_features=7)
                    for i in range(2)})
    res_h = run_tenant_lanes(lanes_h, quiet=True, stacked=False)
    assert_all_ok(res_s)
    assert_all_ok(res_h)
    for name in lanes_s:
        assert model_bytes(lanes_s[name]) == model_bytes(lanes_h[name])
    assert lane_metrics().buckets.value == 2.0
    assert delta["dispatches"] == 2  # one per bucket
    assert delta["stacked"] == 4
    assert delta["padded"] == 0


# --------------------------------------------------------------- isolation
def test_gate_fail_isolated_from_neighbors(tmp_path):
    """A lane that can never clear its gate keeps publishing nothing
    after cycle 0 — its bucket peers' bytes are untouched."""
    lanes_s = make_lanes(tmp_path, "stacked", 2, cycles=2)
    lanes_s["picky"] = lane_kwargs(tmp_path, "stacked", "picky", 999,
                                   cycles=2, min_delta=1e9)
    res_s = run_tenant_lanes(lanes_s, quiet=True, stacked=True,
                             window_sec=1.0)
    lanes_h = make_lanes(tmp_path, "solo", 2, cycles=2)
    res_h = run_tenant_lanes(lanes_h, quiet=True, stacked=False)
    assert_all_ok(res_s)
    assert_all_ok(res_h)
    assert res_s["picky"]["summary"]["gate_failed"] >= 1
    for name in lanes_h:
        assert model_bytes(lanes_s[name]) == model_bytes(lanes_h[name])


class _CrashOnCycle(DataSource):
    """Healthy synthetic cycles except one poisoned cycle index."""

    def __init__(self, crash_cycle, seed):
        self.crash_cycle = crash_cycle
        self.inner = SyntheticDataSource(n_rows=64, n_features=4,
                                         seed=seed)

    def next_cycle(self, cycle):
        if cycle == self.crash_cycle:
            raise RuntimeError("poisoned source cycle")
        return self.inner.next_cycle(cycle)


def test_crashing_lane_isolated_from_neighbors(tmp_path):
    """One lane's source raising mid-run is contained in that lane's
    error count; neighbors' bytes stay bit-identical to solo."""
    lanes_s = make_lanes(tmp_path, "stacked", 2, cycles=2)
    lanes_s["crashy"] = lane_kwargs(
        tmp_path, "stacked", "crashy", 999, cycles=2,
        source=_CrashOnCycle(crash_cycle=1, seed=999))
    res_s = run_tenant_lanes(lanes_s, quiet=True, stacked=True,
                             window_sec=0.2)
    lanes_h = make_lanes(tmp_path, "solo", 2, cycles=2)
    res_h = run_tenant_lanes(lanes_h, quiet=True, stacked=False)
    assert_all_ok(res_h)
    # the trainer contains per-cycle errors: status ok, errors counted
    assert res_s["crashy"]["status"] == "ok"
    assert res_s["crashy"]["summary"]["errors"] >= 1
    for name in lanes_h:
        assert res_s[name]["status"] == "ok"
        assert model_bytes(lanes_s[name]) == model_bytes(lanes_h[name])


# ------------------------------------------------------------- kill switch
def test_lane_stack_env_kill_switch(tmp_path, monkeypatch):
    """XGBTPU_LANE_STACK=0 routes run_tenant_lanes through the host
    loop: zero stacked dispatches, same bytes."""
    monkeypatch.setenv("XGBTPU_LANE_STACK", "0")
    lanes_off = make_lanes(tmp_path, "env_off", 2, cycles=1)
    before = lane_counts()
    res_off = run_tenant_lanes(lanes_off, quiet=True)
    assert counts_delta(before)["dispatches"] == 0
    monkeypatch.setenv("XGBTPU_LANE_STACK", "1")
    lanes_on = make_lanes(tmp_path, "env_on", 2, cycles=1)
    before = lane_counts()
    res_on = run_tenant_lanes(lanes_on, quiet=True, window_sec=1.0)
    assert counts_delta(before)["dispatches"] == 1
    assert_all_ok(res_off)
    assert_all_ok(res_on)
    for name in lanes_on:
        assert model_bytes(lanes_on[name]) == model_bytes(lanes_off[name])


# ------------------------------------------------- steady-bucket carry path
def test_carry_fast_path_reuses_stack_and_stays_identical():
    """Long-lived boosters re-dispatching the same bucket hit the carry
    (no re-stack after the first dispatch) and the bytes still match a
    round-for-round solo run."""
    def boosters(tag):
        out = []
        for i in range(4):
            rng = np.random.RandomState(300 + i)
            X = rng.rand(64, 4).astype(np.float32)
            y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
            d = xgb.DMatrix(X, label=y)
            b = xgb.Booster(dict(PARAMS, seed=300 + i), [d])
            out.append((b, d))
        return out

    gang = LaneGang(expected=0)
    stacked = boosters("stacked")
    before = lane_counts()
    for cycle in range(3):
        arrs = []
        for i, (b, d) in enumerate(stacked):
            spec, why = b.fused_lane_spec(d, cycle * 2, 2)
            assert spec is not None, why
            arrs.append(_Arrival(f"lane{i}", spec, lambda it: None))
        gang._dispatch_bucket(_bucket_of(arrs[0].spec), arrs)
        for a in arrs:
            assert a.exc is None
    delta = counts_delta(before)
    assert delta["dispatches"] == 3
    assert delta["restacks"] == 1  # cycles 2..3 rode the carry

    solo = boosters("solo")
    for cycle in range(3):
        for b, d in solo:
            b.update_many(d, cycle * 2, 2)
    for (bs, _), (bh, _) in zip(stacked, solo):
        assert bs.save_raw() == bh.save_raw()


def test_steady_bucket_recompiles_nothing(tmp_path, recompile_guard):
    """A second run over an already-warm bucket shape stays inside the
    jit caches end to end (ANALYSIS.md XGT001)."""
    warm = make_lanes(tmp_path, "warm", 2, cycles=1)
    assert_all_ok(run_tenant_lanes(warm, quiet=True, stacked=True,
                                   window_sec=1.0))
    again = make_lanes(tmp_path, "again", 2, cycles=1)
    with recompile_guard.expect(0):
        assert_all_ok(run_tenant_lanes(again, quiet=True, stacked=True,
                                       window_sec=1.0))


# --------------------------------------------------------- host-loop bound
class _TrackingSource(DataSource):
    """Counts concurrently-active next_cycle calls across instances."""
    lock = threading.Lock()
    cur = 0
    peak = 0

    def __init__(self, seed):
        self.inner = SyntheticDataSource(n_rows=64, n_features=4,
                                         seed=seed)

    def next_cycle(self, cycle):
        cls = _TrackingSource
        with cls.lock:
            cls.cur += 1
            cls.peak = max(cls.peak, cls.cur)
        try:
            import time
            time.sleep(0.05)
            return self.inner.next_cycle(cycle)
        finally:
            with cls.lock:
                cls.cur -= 1


def test_host_loop_bounds_workers(tmp_path):
    _TrackingSource.cur = _TrackingSource.peak = 0
    lanes = {f"t{i}": lane_kwargs(tmp_path, "bound", f"t{i}", 400 + i,
                                  cycles=1,
                                  source=_TrackingSource(400 + i))
             for i in range(6)}
    res = run_tenant_lanes(lanes, quiet=True, stacked=False,
                           max_workers=2)
    assert_all_ok(res)
    assert 1 <= _TrackingSource.peak <= 2


# ------------------------------------------------------------ lane seeding
def test_lane_name_derives_seed(tmp_path):
    """Two tenants with identical data/params but different names grow
    different models (per-lane seed from the NAME); an explicit seed
    param pins them back together."""
    def pair(mode, params):
        return {name: lane_kwargs(tmp_path, mode, name, 7, cycles=1,
                                  params=params)
                for name in ("alpha", "beta")}

    lanes = pair("named", PARAMS)
    assert_all_ok(run_tenant_lanes(lanes, quiet=True, stacked=False))
    assert model_bytes(lanes["alpha"]) != model_bytes(lanes["beta"])

    pinned = pair("pinned", dict(PARAMS, seed=5))
    assert_all_ok(run_tenant_lanes(pinned, quiet=True, stacked=False))
    assert model_bytes(pinned["alpha"]) == model_bytes(pinned["beta"])


def test_stacked_dispatch_failure_fails_the_run(tmp_path, monkeypatch):
    """A stacked dispatch that cannot compile/run is NOT a tenant fault
    (contrast the gate-fail and crash cases above): every lane of the
    bucket ends ``status: error`` — none quietly re-runs solo to a
    green result with the stacked program never having worked."""
    from xgboost_tpu.models import gbtree

    def refuse(*a, **kw):
        raise RuntimeError("Mosaic failed to compile the lane kernel")

    monkeypatch.setattr(gbtree, "_scan_rounds_lanes", refuse)
    lanes = make_lanes(tmp_path, "refused", 2, cycles=2)
    solo_before = dict(lane_metrics().solo.values())
    res = run_tenant_lanes(lanes, quiet=True, stacked=True,
                           window_sec=1.0)
    for name, r in res.items():
        assert r["status"] == "error", (name, r)
        assert "stacked dispatch failed" in r["error"]
        assert "Mosaic failed to compile" in r["error"]
        assert not os.path.exists(lanes[name]["publish_path"])
    assert dict(lane_metrics().solo.values()) == solo_before
