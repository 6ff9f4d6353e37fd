"""Chaos suite: crash-safe persistence + failure-path hardening
(xgboost_tpu.reliability; design in RELIABILITY.md).

Acceptance criteria covered here:
(a) a kill at any injected fault point during save_model/checkpointing
    never yields a silently-wrong model — the torn file fails
    verification, the checkpoint ring falls back to the older replica,
    and resumed training finishes BIT-identical to an uninterrupted
    run;
(b) overwriting the served model file with corrupt bytes under
    concurrent traffic causes zero failed predictions, exactly one
    engine build attempt until the file changes, and a /healthz that
    reports the reload error;
(c) SIGTERM drain finishes in-flight requests and 503s new ones;
(d) abandoned requests are shed before device dispatch.

Every fault is injected through reliability/faults.py seams inside the
REAL write/read/reload code paths — no test doubles.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs import ServingMetrics, reliability_metrics
from xgboost_tpu.reliability import faults
from xgboost_tpu.reliability.integrity import (ModelIntegrityError,
                                               add_footer, atomic_write,
                                               has_footer, quarantine,
                                               verify_model_bytes)
from xgboost_tpu.serving import MicroBatcher, ModelRegistry, run_server


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    """Every test starts and ends with a disarmed fault registry."""
    faults.clear_faults()
    yield
    faults.clear_faults()


def _train(seed=0, rounds=4, **params):
    rng = np.random.RandomState(seed)
    X = rng.rand(200, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    p = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.4,
         "silent": 1, "seed": seed, **params}
    return xgb.train(p, xgb.DMatrix(X, label=y), rounds), X


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    bst, X = _train()
    path = str(tmp_path_factory.mktemp("reliability") / "m.model")
    bst.save_model(path)
    return bst, X, path


# ----------------------------------------------------------- atomic_write
def test_atomic_write_basic(tmp_path):
    p = tmp_path / "f.bin"
    atomic_write(str(p), b"hello")
    assert p.read_bytes() == b"hello"
    atomic_write(str(p), b"world")  # overwrites atomically
    assert p.read_bytes() == b"world"
    # no tmp droppings
    assert os.listdir(tmp_path) == ["f.bin"]


def test_atomic_write_preserves_file_mode(tmp_path):
    """mkstemp's private 0600 must not leak to the destination: fresh
    files honor the umask (like plain open did) and overwrites keep
    the file's existing mode."""
    p = tmp_path / "f.bin"
    old = os.umask(0o022)
    try:
        atomic_write(str(p), b"fresh")
        assert os.stat(p).st_mode & 0o777 == 0o644
    finally:
        os.umask(old)
    os.chmod(p, 0o600)  # operator tightened it; overwrite keeps it
    atomic_write(str(p), b"overwrite")
    assert os.stat(p).st_mode & 0o777 == 0o600


def test_atomic_write_failure_keeps_old_content(tmp_path):
    p = tmp_path / "f.bin"
    atomic_write(str(p), b"precious")
    faults.inject("enospc", path_sub="f.bin")
    with pytest.raises(OSError):
        atomic_write(str(p), b"replacement")
    # destination untouched, tmp file cleaned up
    assert p.read_bytes() == b"precious"
    assert os.listdir(tmp_path) == ["f.bin"]


# ------------------------------------------------------- footer/verify
def test_footer_roundtrip_and_detection():
    payload = b"some model bytes" * 100
    raw = add_footer(payload)
    assert has_footer(raw)
    assert verify_model_bytes(raw) == payload
    # bit flip anywhere in the payload is caught
    flipped = bytearray(raw)
    flipped[37] ^= 0x10
    with pytest.raises(ModelIntegrityError, match="CRC32 mismatch"):
        verify_model_bytes(bytes(flipped))
    # flip inside the footer itself is caught too (crc won't match)
    flipped2 = bytearray(raw)
    flipped2[-5] = ord("0") if flipped2[-5] != ord("0") else ord("1")
    with pytest.raises(ModelIntegrityError):
        verify_model_bytes(bytes(flipped2))


def test_torn_write_detected_at_several_offsets(model, tmp_path):
    """Satellite: torn-write detection across the whole file — every
    truncation point inside the payload OR the footer raises the typed
    error (never a silently-wrong model, never a crash in np.load
    without the integrity type)."""
    _, _, path = model
    raw = open(path, "rb").read()
    n = len(raw)
    for cut in (8, 100, n // 4, n // 2, (9 * n) // 10, n - 30, n - 5, n - 1):
        torn = tmp_path / f"torn_{cut}.model"
        torn.write_bytes(raw[:cut])
        with pytest.raises(ModelIntegrityError):
            xgb.Booster(model_file=str(torn))


def test_footerless_legacy_file_loads_with_warning(model, tmp_path, capfd):
    bst, X, path = model
    raw = open(path, "rb").read()
    legacy = tmp_path / "legacy.model"
    legacy.write_bytes(verify_model_bytes(raw))  # strip the footer
    capfd.readouterr()
    b2 = xgb.Booster(model_file=str(legacy))
    assert "[integrity]" in capfd.readouterr().err
    ref = bst.predict(xgb.DMatrix(X[:10]))
    assert np.array_equal(b2.predict(xgb.DMatrix(X[:10])), ref)


def test_bs64_model_has_footer_and_detects_corruption(model, tmp_path):
    bst, X, path = model
    p = str(tmp_path / "m.b64")
    bst.save_model(p, save_base64=True)
    raw = open(p, "rb").read()
    assert raw.startswith(b"bs64\t") and has_footer(raw)
    assert np.array_equal(xgb.Booster(model_file=p).predict(
        xgb.DMatrix(X[:5])), bst.predict(xgb.DMatrix(X[:5])))
    bad = bytearray(raw)
    bad[20] ^= 0x04
    (tmp_path / "bad.b64").write_bytes(bytes(bad))
    with pytest.raises(ModelIntegrityError):
        xgb.Booster(model_file=str(tmp_path / "bad.b64"))


def test_quarantine_moves_file_aside(tmp_path):
    p = tmp_path / "x.model"
    p.write_bytes(b"junk")
    q = quarantine(str(p))
    assert not p.exists() and q.endswith(".corrupt")
    # a second quarantine of the same name numbers itself
    p.write_bytes(b"junk2")
    q2 = quarantine(str(p))
    assert q2 != q and os.path.exists(q2)


def test_quarantine_fsyncs_parent_dir(tmp_path, monkeypatch):
    """ISSUE 17 satellite: the quarantine rename must be made DURABLE
    (directory fsync) — a crash right after quarantining a corrupt
    ring member must not resurrect it into the ring on reboot."""
    fsynced = []
    real = os.fsync
    monkeypatch.setattr(
        os, "fsync", lambda fd: (fsynced.append(fd), real(fd))[1])
    p = tmp_path / "z.model"
    p.write_bytes(b"junk")
    q = quarantine(str(p))
    assert not p.exists() and os.path.exists(q)
    assert fsynced, "quarantine rename was not fsynced"


# ------------------------------------------------- fault registry itself
def test_fault_spec_errors_fail_loud_and_arm_nothing(tmp_path):
    """ISSUE 17 satellite: every malformed spec raises the typed
    FaultSpecError at ARM time, emits a ``faults.invalid_spec`` obs
    event, and arms NOTHING — including when the bad entry TRAILS
    valid ones (two-phase parse), so a chaos run with a typo'd spec
    dies at startup instead of passing with untested faults."""
    from xgboost_tpu.obs import events
    log = str(tmp_path / "obs.jsonl")
    events.configure_log(log)
    bad_specs = (
        "bogus_kind@ckpt",          # unknown kind
        "torn_write=abc@ckpt",      # non-numeric arg
        "torn_write=128@ckpt*0",    # times < 1
        "bit_flip@ckpt*zz",         # non-integer times
        "=3@x",                     # empty kind
        "   ;  ;",                  # spec arms nothing
        "torn_write=128@ckpt;bogus@x",  # trailing typo: NOTHING armed
    )
    try:
        for bad in bad_specs:
            with pytest.raises(faults.FaultSpecError):
                faults.install_spec(bad)
            assert not faults.active(), bad
    finally:
        events.configure_log(None)
    recs = [json.loads(line) for line in open(log)]
    names = [r["name"] for r in recs if r.get("kind") == "event"]
    assert names.count("faults.invalid_spec") == len(bad_specs)


def test_gang_fault_kinds_fire_at_coordinate():
    """The gang seam: ``host_loss``/``partition`` arm from a spec and
    fire exactly at their ``t<trial>.r<rank>.v<version>.`` coordinate,
    once each."""
    faults.install_spec("host_loss@t0.r0.v2.;partition=3.5@t0.r1.v4.")
    assert faults.gang_fault("t0.r0.v1.") == []
    assert faults.gang_fault("t1.r0.v2.") == []  # other trial: no fire
    assert faults.gang_fault("t0.r0.v2.") == [("host_loss", None)]
    assert faults.gang_fault("t0.r0.v2.") == []  # fired once, disarmed
    assert faults.gang_fault("t0.r1.v4.") == [("partition", 3.5)]


def test_fault_spec_parsing():
    faults.install_spec("torn_write=128@ckpt-000003;slow_read=0.01#3;enospc")
    assert faults.active()
    faults.clear_faults()
    assert not faults.active()
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.inject("meteor_strike")


def test_fault_spec_star_times_survives_config_comments(tmp_path):
    """`*times` is the config-file-safe multiplier: `#` would be
    stripped as a comment by parse_config_file."""
    from xgboost_tpu.config import parse_config_file
    cfg = tmp_path / "chaos.conf"
    cfg.write_text("faults = slow_read=0.001@probe*3\n")
    pairs = dict(parse_config_file(str(cfg)))
    faults.install_spec(pairs["faults"])
    p = str(tmp_path / "probe.bin")
    open(p, "wb").write(b"x")
    from xgboost_tpu.reliability.integrity import read_file
    n0 = faults.fired("slow_read")
    for _ in range(4):
        read_file(p)
    assert faults.fired("slow_read") - n0 == 3  # armed 3 times, not 1


def test_bit_flip_on_empty_payload_is_noop(tmp_path):
    """Chaos on a zero-byte file must not crash the injector itself."""
    faults.inject("read_flip", 5, path_sub="empty")
    p = str(tmp_path / "empty.bin")
    open(p, "wb").write(b"")
    from xgboost_tpu.reliability.integrity import read_file
    assert read_file(p) == b""


def test_injected_faults_fire_once_and_count(tmp_path):
    p = str(tmp_path / "f.bin")
    n0 = faults.fired("torn_write")
    faults.inject("torn_write", 3, path_sub="f.bin", times=1)
    atomic_write(p, b"0123456789")
    assert open(p, "rb").read() == b"012"          # torn at byte 3
    atomic_write(p, b"0123456789")                 # disarmed: full write
    assert open(p, "rb").read() == b"0123456789"
    assert faults.fired("torn_write") - n0 == 1
    assert reliability_metrics().faults_injected.value >= 1


def test_path_filter_scopes_faults(tmp_path):
    faults.inject("bit_flip", 0, path_sub="target")
    other = str(tmp_path / "other.bin")
    atomic_write(other, b"AAAA")
    assert open(other, "rb").read() == b"AAAA"     # filter did not match
    target = str(tmp_path / "target.bin")
    atomic_write(target, b"AAAA")
    assert open(target, "rb").read() != b"AAAA"    # flipped


# ------------------------------------------------- save_model hardening
def test_save_model_is_atomic_under_enospc(model, tmp_path):
    """Satellite: a failed save never tears the destination — the old
    (watched!) model file survives byte-identical."""
    bst, _, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    before = open(p, "rb").read()
    faults.inject("enospc", path_sub="m.model")
    with pytest.raises(OSError):
        bst.save_model(p)
    assert open(p, "rb").read() == before
    # and the file still verifies + loads
    xgb.Booster(model_file=p)


def test_torn_final_model_write_is_never_silently_wrong(tmp_path):
    """Acceptance (a), model_out leg: a torn write of the FINAL model
    fails verification on load instead of producing wrong predictions."""
    data = tmp_path / "train.libsvm"
    _write_libsvm(str(data))
    out = tmp_path / "final.model"
    faults.inject("torn_write", 200, path_sub="final.model")
    from xgboost_tpu.cli import main
    assert main([f"data={data}", "task=train", "num_round=3", "silent=2",
                 "objective=binary:logistic", "max_bin=16",
                 f"model_out={out}"]) == 0
    with pytest.raises(ModelIntegrityError):
        xgb.Booster(model_file=str(out))


# ----------------------------------------------- checkpoint-ring fallback
def _write_libsvm(path, n=300, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] > 0.5).astype(int)
    with open(path, "w") as fh:
        for i in range(n):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(f))
            fh.write(f"{y[i]} {feats}\n")


def _model_state(path):
    return xgb.Booster(model_file=str(path)).gbtree.get_state()


def test_load_checkpoint_falls_back_past_truncated_newest(model, tmp_path):
    """Satellite regression: a truncated newest ckpt-* member no longer
    aborts the gang restart — the older replica loads, the bad file is
    quarantined."""
    from xgboost_tpu.cli import _load_checkpoint, _save_checkpoint
    bst_a, X, _ = model
    bst_b, _ = _train(seed=7, rounds=6, max_depth=2)
    ck = str(tmp_path / "ck")
    _save_checkpoint(ck, bst_a, 3)
    _save_checkpoint(ck, bst_b, 4)
    newest = os.path.join(ck, "ckpt-000004.model")
    raw = open(newest, "rb").read()
    open(newest, "wb").write(raw[:len(raw) // 2])  # truncate mid-file

    rm = reliability_metrics()
    fb0, q0 = rm.ring_fallbacks.value, rm.quarantines.value
    fresh = xgb.Booster()
    got, version = _load_checkpoint(ck, fresh, {})
    assert version == 3  # fell back to the older ring member
    ref = bst_a.predict(xgb.DMatrix(X[:10]))
    assert np.array_equal(got.predict(xgb.DMatrix(X[:10])), ref)
    assert os.path.exists(newest + ".corrupt")
    assert not os.path.exists(newest)
    assert rm.ring_fallbacks.value - fb0 == 1
    assert rm.quarantines.value - q0 == 1
    # a later _save_checkpoint of version 4 replaces the slot cleanly
    _save_checkpoint(ck, bst_b, 4)
    got2, v2 = _load_checkpoint(ck, xgb.Booster(), {})
    assert v2 == 4


def test_all_ring_members_corrupt_starts_fresh(tmp_path):
    from xgboost_tpu.cli import _load_checkpoint
    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "ckpt-000001.model").write_bytes(b"garbage")
    (ck / "ckpt-000002.model").write_bytes(b"PK\x03\x04 torn")
    bst, version = _load_checkpoint(str(ck), xgb.Booster(), {})
    assert version == 0
    assert sorted(f for f in os.listdir(ck) if f.endswith(".corrupt")) == [
        "ckpt-000001.model.corrupt", "ckpt-000002.model.corrupt"]


def test_transient_read_error_does_not_quarantine(model, tmp_path):
    """A transient OSError (EIO/EMFILE blip) on a ring member falls
    back WITHOUT quarantining the possibly-good file — the next
    restart can retry it."""
    from xgboost_tpu.cli import _load_checkpoint, _save_checkpoint
    bst_a, X, _ = model
    bst_b, _ = _train(seed=8, rounds=6, max_depth=2)
    ck = str(tmp_path / "ck")
    _save_checkpoint(ck, bst_a, 3)
    _save_checkpoint(ck, bst_b, 4)
    newest = os.path.join(ck, "ckpt-000004.model")
    # simulate a transient I/O failure: open() raises OSError (ENOENT
    # via a dangling symlink — chmod tricks don't work under root)
    os.rename(newest, newest + ".real")
    os.symlink(newest + ".gone", newest)
    try:
        got, version = _load_checkpoint(ck, xgb.Booster(), {})
        assert version == 3  # older member served this restart
        assert os.path.lexists(newest)  # NOT renamed to .corrupt
        assert not os.path.exists(newest + ".corrupt")
    finally:
        os.remove(newest)
        os.rename(newest + ".real", newest)
    # blip cleared: the next restart loads the newest member normally
    got2, v2 = _load_checkpoint(ck, xgb.Booster(), {})
    assert v2 == 4


def test_drain_grace_expiry_is_bounded_with_wedged_request(model, tmp_path):
    """A wedged device call must not defeat the drain grace: drain()
    returns once the grace expires even though the request never
    finishes (its daemon handler thread is reaped at process exit)."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    base = f"http://127.0.0.1:{srv.port}"
    body = b"0.1,0.2,0.3,0.4,0.5"
    gate, entered = threading.Event(), threading.Event()
    real_fn = srv.batcher.predict_fn

    def wedged(Xq, **kw):
        entered.set()
        gate.wait(30.0)  # "wedged" until the test cleans up
        return real_fn(Xq, **kw)

    srv.batcher.predict_fn = wedged
    t = threading.Thread(target=lambda: urllib.request.urlopen(
        urllib.request.Request(base + "/predict", data=body,
                               method="POST")), daemon=True)
    t.start()
    assert entered.wait(5.0)
    # release the wedge shortly AFTER the grace expires, so close()'s
    # bounded worker join doesn't stretch the test
    threading.Timer(0.6, gate.set).start()
    t0 = time.perf_counter()
    dur = srv.drain(grace=0.2)
    assert srv.state == "stopped"
    assert time.perf_counter() - t0 < 10.0  # bounded, not wedged-forever
    assert dur >= 0.2
    gate.set()


def test_total_ring_failure_restores_booster_config(tmp_path):
    """A checkpoint whose HEADER parses but whose state arrays are
    corrupt must not leak its param/objective into the fresh-start
    booster when every ring member fails."""
    import io
    from xgboost_tpu.cli import _load_checkpoint
    ck = tmp_path / "ck"
    ck.mkdir()
    # valid footer + valid npz + valid header, corrupt state: load gets
    # past the param/objective assignment, then raises in from_state
    header = {"magic": "xgbtpu001",
              "param": {"objective": "multi:softmax", "num_class": 7},
              "num_feature": 99, "attributes": {"evil": "1"},
              "best_iteration": 5}
    buf = io.BytesIO()
    np.savez(buf, header=np.frombuffer(json.dumps(header).encode(),
                                       np.uint8),
             bogus=np.zeros(3, np.float32))
    (ck / "ckpt-000002.model").write_bytes(add_footer(buf.getvalue()))

    bst = xgb.Booster({"objective": "binary:logistic"})
    got, version = _load_checkpoint(str(ck), bst,
                                    {"objective": "binary:logistic"})
    assert version == 0
    assert os.path.exists(ck / "ckpt-000002.model.corrupt")
    # the caller's config survived; nothing from the corrupt header did
    assert got.param.objective == "binary:logistic"
    assert got.num_feature == 0 and got.attributes == {}
    assert got.best_iteration == -1 and got.gbtree is None


def test_kill_plus_torn_checkpoint_recovers_bit_identical(tmp_path, capfd):
    """Acceptance (a), the full gauntlet: the newest ring member is TORN
    by an injected fault, the worker then dies at an injected collective
    coordinate, the keepalive restart quarantines the torn member, falls
    back to the older replica, and the finished model is bit-identical
    to an uninterrupted run."""
    from xgboost_tpu.cli import main
    data = tmp_path / "train.libsvm"
    _write_libsvm(str(data))
    common = [f"data={data}", "task=train", "num_round=5", "silent=2",
              "objective=binary:logistic", "max_depth=3", "eta=0.5",
              "max_bin=16",
              # per-round segments: mock replay no longer blocks fusion,
              # and this test's torn-member/fallback choreography needs
              # the ring written at every round boundary
              "rounds_per_dispatch=1"]
    m_ref = tmp_path / "ref.model"
    assert main(common + [f"model_out={m_ref}",
                          f"checkpoint_dir={tmp_path / 'ck_ref'}"]) == 0

    # round 2's checkpoint (version 3) is torn at byte 100; the worker
    # dies entering round 3 — restart must fall back to version 2
    faults.inject("torn_write", 100, path_sub="ckpt-000003")
    capfd.readouterr()
    m_got = tmp_path / "got.model"
    rm = reliability_metrics()
    fb0 = rm.ring_fallbacks.value
    assert main(common + [f"model_out={m_got}",
                          f"checkpoint_dir={tmp_path / 'ck_got'}",
                          "mock=3,0,0", "keepalive=1"]) == 0
    err = capfd.readouterr().err
    assert err.count("[mock]") == 1, err           # the death fired
    assert "falling back" in err                   # the fallback fired
    assert "resume at round 2" in err              # older replica used
    assert rm.ring_fallbacks.value - fb0 == 1

    ref, got = _model_state(m_ref), _model_state(m_got)
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)


def test_cli_faults_param_installs_spec(tmp_path):
    """The faults= CLI parameter (env-free injection for subprocess
    drivers) reaches the write seam."""
    from xgboost_tpu.cli import main
    data = tmp_path / "train.libsvm"
    _write_libsvm(str(data))
    out = tmp_path / "m.model"
    assert main([f"data={data}", "task=train", "num_round=2", "silent=2",
                 "objective=binary:logistic", "max_bin=16",
                 f"model_out={out}", "faults=bit_flip=50@m.model"]) == 0
    with pytest.raises(ModelIntegrityError):
        xgb.Booster(model_file=str(out))


# --------------------------------------------------- registry poisoning
def test_registry_rejects_corrupt_file_before_engine_build(model, tmp_path):
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    bad = bytearray(open(p, "rb").read())
    bad[120] ^= 1
    open(p, "wb").write(bytes(bad))
    with pytest.raises(ModelIntegrityError):
        ModelRegistry(p, warmup=False, poll_sec=0,
                      min_bucket=8, max_bucket=32)


def test_registry_poisons_corrupt_overwrite(model, tmp_path):
    """Satellite: a persistently corrupt model file is built exactly
    once, then hashed-and-rejected (no re-warm) until the file changes
    again."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    good = open(p, "rb").read()
    reg = ModelRegistry(p, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    ref = bst.predict(xgb.DMatrix(X[:8]))
    assert np.array_equal(reg.predict(X[:8]), ref)

    bad = bytearray(good)
    bad[99] ^= 0x20
    open(p, "wb").write(bytes(bad))
    a0 = reg.build_attempts
    p0 = reliability_metrics().poisoned_reloads.value
    assert reg.check_reload() is False
    assert reg.build_attempts - a0 == 1
    assert reg.poisoned and reg.reload_failures == 1
    assert "CRC32" in reg.last_reload_error
    for _ in range(5):  # the 1 s poll, compressed: NO rebuild, NO rewarm
        assert reg.check_reload() is False
    assert reg.build_attempts - a0 == 1
    assert reliability_metrics().poisoned_reloads.value > p0
    # old model still serving
    assert np.array_equal(reg.predict(X[:8]), ref)

    # rewriting the SAME bad bytes (new mtime) is still rejected by hash
    time.sleep(0.01)
    open(p, "wb").write(bytes(bad))
    assert reg.check_reload() is False
    assert reg.build_attempts - a0 == 1

    # a genuinely new good model clears the poisoning
    bst_b, _ = _train(seed=5, rounds=6, max_depth=2)
    bst_b.save_model(p)
    assert reg.check_reload() is True
    assert reg.version == 2 and not reg.poisoned
    assert reg.last_reload_error is None
    assert np.array_equal(reg.predict(X[:8]),
                          bst_b.predict(xgb.DMatrix(X[:8])))


def test_registry_injected_reload_failure_poisons(model, tmp_path):
    """The reload seam (faults.check('reload')) poisons like organic
    corruption: one build attempt, retried only on the next change."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    reg = ModelRegistry(p, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    bst_b, _ = _train(seed=3, rounds=5, max_depth=2)
    faults.inject("reload", path_sub="m.model")
    bst_b.save_model(p)
    a0 = reg.build_attempts
    assert reg.check_reload() is False
    assert reg.poisoned and "InjectedFault" in reg.last_reload_error
    assert reg.check_reload() is False
    assert reg.build_attempts - a0 == 1
    # fault disarmed after firing once; the NEXT file change reloads
    bst_b.save_model(p)       # same content... poisoned hash matches
    assert reg.check_reload() is False
    bst_c, _ = _train(seed=4, rounds=5, max_depth=2)
    bst_c.save_model(p)
    assert reg.check_reload() is True
    assert reg.version == 2


def test_rollback_to_live_content_clears_degraded(model, tmp_path):
    """Operator remediation by ROLLING BACK the file: restoring the
    live model's exact bytes clears the poisoned/degraded state (the
    on-disk file is no longer known-bad) without a reload."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    good = open(p, "rb").read()
    reg = ModelRegistry(p, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    bad = bytearray(good)
    bad[111] ^= 1
    open(p, "wb").write(bytes(bad))
    assert reg.check_reload() is False
    assert reg.poisoned
    open(p, "wb").write(good)              # roll the push back
    assert reg.check_reload() is False     # same content as live: no-op
    assert not reg.poisoned and reg.last_reload_error is None
    assert reg.version == 1


def test_forced_reload_sees_through_preserved_stat(model, tmp_path):
    """A rewrite that preserves mtime AND size (rsync -a / cp -p of a
    same-sized file) is invisible to the stat-compare poll by design;
    check_reload(force=True) must re-read and detect it anyway."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    st = os.stat(p)
    reg = ModelRegistry(p, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    bad = bytearray(open(p, "rb").read())
    bad[123] ^= 1                                  # same size
    open(p, "wb").write(bytes(bad))
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns))  # same mtime
    assert reg.check_reload() is False             # poll: blind, by design
    assert not reg.poisoned                        # ...and unaware
    assert reg.check_reload(force=True) is False   # forced: READ the file
    assert reg.poisoned and "CRC32" in reg.last_reload_error
    ref = bst.predict(xgb.DMatrix(X[:5]))
    assert np.array_equal(reg.predict(X[:5]), ref)  # old model serving


def test_forced_reload_retries_after_transient_failure(model, tmp_path):
    """check_reload(force=True) — the /-/reload endpoint — bypasses the
    poisoned skip: a GOOD file whose first build failed transiently
    (device hiccup, injected fault) is retried on demand instead of
    being pinned out until its bytes change."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    reg = ModelRegistry(p, warmup=False, poll_sec=0,
                        min_bucket=8, max_bucket=32)
    bst_b, _ = _train(seed=6, rounds=5, max_depth=2)
    faults.inject("reload", path_sub="m.model")  # fires ONCE (transient)
    bst_b.save_model(p)
    assert reg.check_reload() is False            # transient failure
    assert reg.poisoned
    assert reg.check_reload() is False            # unforced: skipped
    assert reg.check_reload(force=True) is True   # forced: retried, lives
    assert reg.version == 2 and not reg.poisoned
    assert np.array_equal(reg.predict(X[:6]),
                          bst_b.predict(xgb.DMatrix(X[:6])))


# ------------------------------------------------ serving under traffic
def test_corrupt_overwrite_under_traffic_zero_failures(model, tmp_path):
    """Acceptance (b): corrupt bytes hit the watched file mid-traffic —
    zero failed predictions, one build attempt, /healthz degraded with
    the error, then recovery on the next good write."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    good = open(p, "rb").read()
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    base = f"http://127.0.0.1:{srv.port}"
    body = "\n".join(",".join(f"{v:.6f}" for v in r)
                     for r in X[:5]).encode()

    def post(route, data=b""):
        return json.load(urllib.request.urlopen(urllib.request.Request(
            base + route, data=data, method="POST")))

    stop = threading.Event()
    errors, n_ok = [], [0]

    def hammer():
        while not stop.is_set():
            try:
                post("/predict", body)
                n_ok[0] += 1
            except BaseException as e:  # noqa: BLE001 — recorded, asserted
                errors.append(e)

    try:
        ts = [threading.Thread(target=hammer) for _ in range(3)]
        for t in ts:
            t.start()
        time.sleep(0.15)
        bad = bytearray(good)
        bad[140] ^= 1
        open(p, "wb").write(bytes(bad))
        a0 = srv.registry.build_attempts
        assert post("/-/reload")["reloaded"] is False  # one build, fails
        for _ in range(3):  # the poll loop: hash-rejected, no rebuild
            assert srv.registry.check_reload() is False
        assert srv.registry.build_attempts - a0 == 1  # exactly one build
        h = json.load(urllib.request.urlopen(base + "/healthz"))
        assert h["status"] == "degraded"
        assert h["reload_failures"] == 1
        assert "CRC32" in h["last_reload_error"]
        time.sleep(0.1)
        stop.set()
        for t in ts:
            t.join(10.0)
        assert not errors, f"requests failed during corruption: {errors[:3]}"
        assert n_ok[0] > 0
        # recovery: good model B goes live, healthz back to ok
        bst_b, _ = _train(seed=21, rounds=6, max_depth=2)
        bst_b.save_model(p)
        assert post("/-/reload")["reloaded"] is True
        h = json.load(urllib.request.urlopen(base + "/healthz"))
        assert h["status"] == "ok" and h["model_version"] == 2
        assert h["last_reload_error"] is None
    finally:
        stop.set()
        srv.shutdown()


def test_drain_state_machine(model, tmp_path):
    """Acceptance (c): drain stops admitting /predict (503), finishes
    the in-flight request, reports state via /healthz, records the
    drain duration, then stops."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    base = f"http://127.0.0.1:{srv.port}"
    body = "\n".join(",".join(f"{v:.6f}" for v in r)
                     for r in X[:3]).encode()
    # make the in-flight request controllable: gate the batcher's
    # predict_fn (the REAL engine runs once the gate opens)
    gate, entered = threading.Event(), threading.Event()
    real_fn = srv.batcher.predict_fn

    def gated(Xq, **kw):
        entered.set()
        gate.wait(10.0)
        return real_fn(Xq, **kw)

    srv.batcher.predict_fn = gated
    result = [None]

    def inflight():
        result[0] = json.load(urllib.request.urlopen(
            urllib.request.Request(base + "/predict", data=body,
                                   method="POST")))

    t = threading.Thread(target=inflight)
    t.start()
    assert entered.wait(5.0)
    assert srv.state == "serving" and srv.inflight == 1
    drain_dur = [None]
    dt = threading.Thread(target=lambda: drain_dur.__setitem__(
        0, srv.drain(grace=10.0)))
    dt.start()
    for _ in range(200):
        if srv.state == "draining":
            break
        time.sleep(0.01)
    assert srv.state == "draining"
    # healthz still answers and reports the state
    h = json.load(urllib.request.urlopen(base + "/healthz"))
    assert h["state"] == "draining"
    # new predictions are refused with 503
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(urllib.request.Request(
            base + "/predict", data=body, method="POST"))
    assert ei.value.code == 503
    # the in-flight request finishes successfully
    gate.set()
    t.join(10.0)
    dt.join(15.0)
    assert result[0] is not None and result[0]["rows"] == 3
    assert srv.state == "stopped"
    assert drain_dur[0] is not None and drain_dur[0] > 0
    assert reliability_metrics().drain_seconds.value > 0


def test_sigterm_handler_triggers_drain(model, tmp_path):
    """The SIGTERM path: the handler spawns the drain (it cannot run on
    the serving thread), ending at state=stopped."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    try:
        import signal as _signal
        srv._handle_sigterm(_signal.SIGTERM, None)
        for _ in range(500):
            if srv.state == "stopped":
                break
            time.sleep(0.01)
        assert srv.state == "stopped"
    finally:
        srv.shutdown()


def test_oversized_body_rejected_without_buffering(model, tmp_path):
    """A Content-Length beyond serve_max_body_mb is refused with 413
    BEFORE any body bytes are read (reject-don't-buffer at the HTTP
    layer too)."""
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     max_body_mb=0.001, quiet=True, block=False)  # 1 KiB
    try:
        base = f"http://127.0.0.1:{srv.port}"
        small = b"0.1,0.2,0.3,0.4,0.5"
        r = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/predict", data=small, method="POST")))
        assert r["rows"] == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict", data=b"x" * 4096, method="POST"))
        assert ei.value.code == 413
    finally:
        srv.shutdown()


def test_libsvm_out_of_range_index_is_client_error(model, tmp_path):
    """A libsvm row addressing a feature beyond the model's width is a
    400 (like the CSV too-many-columns path), not silently-dropped
    features and a confident wrong answer."""
    from xgboost_tpu.serving.http import parse_libsvm_rows
    with pytest.raises(ValueError, match="out of range"):
        parse_libsvm_rows("1 2:0.5 40:0.25", num_feature=5)
    # in range still parses (label column tolerated)
    out = parse_libsvm_rows("1 2:0.5 4:0.25", num_feature=5)
    assert out.shape == (1, 5) and out[0, 2] == np.float32(0.5)
    bst, X, _ = model
    p = str(tmp_path / "m.model")
    bst.save_model(p)
    srv = run_server(p, port=0, min_bucket=8, max_bucket=32,
                     max_wait_ms=1, poll_sec=0, warmup=False,
                     quiet=True, block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict?format=libsvm", data=b"1 0:0.1 99:0.5",
                method="POST"))
        assert ei.value.code == 400
    finally:
        srv.shutdown()


# --------------------------------------------------- batcher shedding
def test_batcher_sheds_abandoned_requests():
    """Satellite (d): a request whose caller timed out is skipped by the
    worker — no device dispatch for a result nobody reads."""
    release = threading.Event()
    calls = []

    def predict_fn(X, output_margin=False):
        calls.append(X.shape[0])
        release.wait(5.0)
        return np.zeros(X.shape[0], np.float32)

    b = MicroBatcher(predict_fn, max_batch_rows=4, max_wait_ms=1,
                     max_queue_rows=100)
    shed0 = reliability_metrics().shed_requests.value
    try:
        t = threading.Thread(target=lambda: b.submit(np.zeros((4, 2))))
        t.start()
        time.sleep(0.05)  # worker picked up the first batch and blocked
        with pytest.raises(TimeoutError):
            b.submit(np.zeros((2, 2)), timeout=0.05)  # queued + abandoned
        release.set()
        t.join(5.0)
    finally:
        release.set()
        b.close()
    # the abandoned request was never dispatched: only the first batch
    # reached predict_fn
    assert calls == [4]
    assert reliability_metrics().shed_requests.value - shed0 == 1


def test_batcher_sheds_only_abandoned_in_mixed_batch():
    """Abandoned and live requests coalesced into the same batch: the
    live one gets its rows, the abandoned one is dropped."""
    release = threading.Event()
    calls = []

    def predict_fn(X, output_margin=False):
        calls.append(X.shape[0])
        if len(calls) == 1:
            release.wait(5.0)
        return X[:, 0].copy()

    b = MicroBatcher(predict_fn, max_batch_rows=100, max_wait_ms=30,
                     max_queue_rows=1000)
    try:
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((2, 2), np.float32)))
        t1.start()
        time.sleep(0.05)  # worker blocked inside batch 1
        with pytest.raises(TimeoutError):
            b.submit(np.full((3, 2), 7.0, np.float32), timeout=0.05)
        res = [None]
        t2 = threading.Thread(target=lambda: res.__setitem__(
            0, b.submit(np.full((2, 2), 9.0, np.float32), timeout=5.0)))
        t2.start()
        time.sleep(0.05)
        release.set()
        t1.join(5.0)
        t2.join(5.0)
        assert np.array_equal(res[0], np.full(2, 9.0, np.float32))
        # the abandoned 3-row request never contributed to a dispatch
        assert 3 not in calls and 5 not in calls
    finally:
        release.set()
        b.close()


# ------------------------------------------- publish-vs-poll concurrency
def test_concurrent_publish_vs_poll_never_torn_never_double(model,
                                                            tmp_path):
    """ModelRegistry.check_reload racing in-flight atomic publishes
    (the continuous-training pipeline's steady state, PIPELINE.md):
    the poller must never build an engine from torn bytes — every
    publish is atomic_write, so every read observes a complete file —
    and must never build the same content hash twice in a row (the
    live-hash short-circuit)."""
    bst, X, path = model
    p = str(tmp_path / "race.model")
    bst.save_model(p)
    with open(p, "rb") as f:
        raw_a = f.read()
    bst_b, _ = _train(seed=7, rounds=3)
    pb = str(tmp_path / "b.model")
    bst_b.save_model(pb)
    with open(pb, "rb") as f:
        raw_b = f.read()
    assert raw_a != raw_b

    reg = ModelRegistry(p, poll_sec=0, warmup=False,
                        min_bucket=8, max_bucket=16)
    built = []
    orig_build = reg._build_engine

    def recording_build(raw):
        import hashlib
        built.append(hashlib.sha256(raw).hexdigest())
        return orig_build(raw)

    reg._build_engine = recording_build
    base_failures = reg.reload_failures
    stop = threading.Event()

    def publisher():
        flip = False
        while not stop.is_set():
            atomic_write(p, raw_b if flip else raw_a)
            flip = not flip
            time.sleep(0.002)

    def poller():
        while not stop.is_set():
            reg.check_reload()

    threads = [threading.Thread(target=publisher)] + [
        threading.Thread(target=poller) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(1.2)
    stop.set()
    for t in threads:
        t.join(30.0)

    # no torn bytes were ever seen: every build verified + loaded
    assert reg.reload_failures == base_failures
    assert len(built) >= 2  # the race actually exercised reloads
    # never the same content twice in a row (each build was a change)
    assert all(h1 != h2 for h1, h2 in zip(built, built[1:]))

    # and a same-bytes rewrite (mtime changes, content does not) never
    # rebuilds: the short-circuit compares against the LIVE engine hash
    reg.check_reload()
    builds_before = len(built)
    with open(p, "rb") as f:
        current = f.read()
    for _ in range(5):
        atomic_write(p, current)
        reg.check_reload()
    assert len(built) == builds_before
    reg.stop()


# ------------------------------------------------------------- metrics
def test_metrics_page_includes_reliability_counters():
    m = ServingMetrics()
    text = m.render()
    for name in ("xgbtpu_reliability_integrity_failures_total",
                 "xgbtpu_reliability_ckpt_ring_fallbacks_total",
                 "xgbtpu_reliability_quarantined_files_total",
                 "xgbtpu_reliability_poisoned_reload_skips_total",
                 "xgbtpu_reliability_shed_requests_total",
                 "xgbtpu_reliability_drain_seconds"):
        assert name in text, f"{name} missing from /metrics"


# ------------------------------------------------------- chaos driver
@pytest.mark.slow
def test_chaos_loop_driver(tmp_path):
    """The tools/chaos_loop.py driver: every randomized kill/corruption
    run recovers bit-identical and CHAOS.json records it."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import chaos_loop
    finally:
        sys.path.pop(0)
    out = tmp_path / "CHAOS.json"
    rc = chaos_loop.main(["--runs", "3", "--rounds", "5", "--seed", "1",
                          "--out", str(out), "--workdir", str(tmp_path)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["runs"] == 3
    assert report["bit_identical"] == 3
    assert report["mismatches"] == 0
