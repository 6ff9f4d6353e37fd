"""What can be held about the chip path WITHOUT a chip (ISSUE 22).

(a) every ``pallas_call`` site cross-lowers for TPU with
    ``interpret=False`` over ``chip_smoke.kernel_cases`` — the only
    guard the kernels have between chip runs (Pallas -> Mosaic MLIR;
    whether Mosaic then COMPILES it only ``chip_smoke.py --kernels``
    on the chip can say);
(b) the compile-cache helper never sets a directory in code when
    ``JAX_COMPILATION_CACHE_DIR`` is set, and otherwise uses the one
    fixed in-checkout path;
(c) ``chip_smoke.py`` refuses to run without a TPU, cannot run alone,
    and its off-chip rehearsal goes green without ever printing the
    pass line.
"""

import ast
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
SMOKE = os.path.join(REPO, "chip_smoke.py")


# ----------------------------------------------------------- (a) lowering
def test_every_pallas_call_site_lowers_for_tpu():
    import jax

    import chip_smoke
    cases = chip_smoke.kernel_cases(5000)
    kinds = {name.split()[0] for name, _ in cases}
    assert kinds == {"solo", "batched", "lanes", "node-stats", "scan"}
    failed = []
    for name, build in cases:
        fn, args, _ = build(False)          # interpret=False
        try:
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
            assert "tpu_custom_call" in text, "no Mosaic call emitted"
        except Exception as e:
            failed.append(f"{name}: {type(e).__name__}: {str(e)[:300]}")
    assert not failed, "\n".join(failed)


@pytest.mark.parametrize("M,tiles", [(256, 2), (512, 4)])
def test_derived_level_at_many_node_and_feature_tiles_lowers_for_tpu(M,
                                                                     tiles):
    """ISSUE 39's cases: the 256- and 512-node levels built as ``tiles``
    node tiles of 64 left children, over 87 feature tiles whose last
    has one padded slot (F = 695), lowered for TPU: each kernel a
    Mosaic call, the parent level's and the left children's ``tiles``
    node tiles deep.  ``chip_smoke.py --kernels`` runs them on the
    chip, derived == built bit for bit."""
    import re

    import jax

    import chip_smoke
    name, build = next(
        c for c in chip_smoke.kernel_cases(5000)
        if f"F=695 B=256 M={M} int8 derived from {M // 2} left" in c[0])
    fn, args, _ = build(False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"tpu_custom_call.*?-> tensor<(\d+)x", text)
    # the parent level and the left children (M / 128 tiles each), the
    # level built whole, raw and dequantized (M / 64 tiles each)
    assert sorted(int(c) for c in calls) == [tiles, tiles, 2 * tiles,
                                             2 * tiles], (name, calls)


def test_derived_chain_in_one_scan_lowers_for_tpu():
    """PR 39's case of what the grower runs at depth 10: the levels of
    64, 128, 256 and 512 nodes inside one ``lax.scan``, each past the
    first derived from the one above.  Lowered for TPU, the scan's body
    holds one Mosaic call a level: the 64 nodes built whole, then 64,
    128 and 256 left children (1, 1, 2 and 4 node tiles), and the
    128-node block reaches the 256-node level's derivation."""
    import re

    import jax

    import chip_smoke
    name, build = next(c for c in chip_smoke.kernel_cases(5000)
                       if c[0].startswith("scan "))
    assert "64 -> 128 -> 256 -> 512" in name and "F=695" in name
    fn, args, _ = build(False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "stablehlo.while" in text
    calls = re.findall(r"tpu_custom_call.*?-> tensor<(\d+)x", text)
    assert sorted(int(c) for c in calls) == [1, 1, 2, 4], (name, calls)


@pytest.mark.parametrize("fault", [None, "zero 128"])
def test_derived_chain_case_runs_here_and_sees_a_zero_level(fault,
                                                            monkeypatch):
    """The same case run here in interpret mode: right as shipped, and
    its check fails where the 128-node level's block reads zero, the
    fault the chip showed in PR 39."""
    import jax

    import chip_smoke
    from xgboost_tpu.ops import pallas_hist as ph
    if fault:
        derive = ph._derive_level

        def zero_128(parent, left, split, n_node):
            out = derive(parent, left, split, n_node)
            return out * 0 if n_node == 128 else out
        monkeypatch.setattr(ph, "_derive_level", zero_128)
    name, build = next(c for c in chip_smoke.kernel_cases(5000)
                       if c[0].startswith("scan "))
    fn, args, verify = build(True)
    out = jax.jit(fn)(*args)
    if fault is None:
        verify(out)
    else:
        with pytest.raises(SystemExit, match="level 128"):
            verify(out)


@pytest.mark.parametrize("kind,kernel", [
    ("solo", "hist_level_rows"), ("batched", "hist_level_trees"),
    ("lanes", "hist_level_lanes"), ("node-stats", "hist_level_node_stats")])
def test_each_pallas_call_site_names_its_kernel(kind, kernel):
    """Every ``pallas_call`` passes ``name=``: the name a device trace
    shows for the kernel (``%<name>.<n>``) is the program's own choice,
    not the enclosing jit's (OBSERVABILITY.md, kernel names)."""
    import re

    import jax

    import chip_smoke
    name, build = next(c for c in chip_smoke.kernel_cases(5000)
                       if c[0].split()[0] == kind)
    fn, args, _ = build(False)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r"hist_level_\w+", text)) == {kernel}, name


# ------------------------------------------------------ (b) compile cache
@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls WITHOUT applying them: the
    test process must not start writing a persistent cache."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.delenv("XGBTPU_NO_JITCACHE", raising=False)
    return calls


def test_cache_dir_from_env_is_never_set_in_code(config_updates,
                                                 monkeypatch, tmp_path):
    from xgboost_tpu.compile_cache import configure_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert configure_compile_cache() == str(tmp_path / "c")
    keys = [k for k, _ in config_updates]
    assert "jax_compilation_cache_dir" not in keys
    # the keep-everything thresholds still apply
    assert "jax_persistent_cache_min_compile_time_secs" in keys
    assert "jax_persistent_cache_min_entry_size_bytes" in keys


def test_cache_dir_default_is_the_fixed_checkout_path(config_updates,
                                                      monkeypatch):
    from xgboost_tpu.compile_cache import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jitcache")
    assert configure_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in config_updates
    assert configure_compile_cache() == want     # same path every time


def test_cache_opt_out_touches_nothing(config_updates, monkeypatch):
    from xgboost_tpu.compile_cache import configure_compile_cache
    monkeypatch.setenv("XGBTPU_NO_JITCACHE", "1")
    assert configure_compile_cache() is None
    assert config_updates == []


def test_cache_path_is_built_from_nothing_that_moves():
    """No tempfile, pid, clock or randomness anywhere in the module: a
    cache directory that moves between runs never hits."""
    with open(os.path.join(REPO, "xgboost_tpu", "compile_cache.py")) as f:
        tree = ast.parse(f.read())
    imported, attrs = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
        elif isinstance(node, ast.Attribute):
            attrs.add(node.attr)
    assert not imported & {"tempfile", "time", "datetime", "uuid",
                           "random", "secrets"}
    assert not attrs & {"getpid", "getppid", "mkdtemp", "gettempdir"}


def test_entry_points_place_the_cache():
    """cli, serving and the smoke all go through the helper, and
    nothing else sets the directory."""
    for rel in ("xgboost_tpu/cli.py", "xgboost_tpu/serving/__main__.py",
                "chip_smoke.py"):
        with open(os.path.join(REPO, rel)) as f:
            assert "configure_compile_cache()" in f.read(), rel
    offenders = []
    for root, _, files in os.walk(REPO):
        if any(p in root for p in (".git", "_archive_check",
                                   "chiprun_out", "/tests")):
            continue
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and not path.endswith(
                    "xgboost_tpu/compile_cache.py"):
                with open(path) as f:
                    if "jax_compilation_cache_dir" in f.read():
                        offenders.append(path)
    assert not offenders, offenders


# ------------------------------------------------------------- (c) smoke
def _run(args, cwd=REPO, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", XGBTPU_NO_JITCACHE="1")
    e.update(env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=900)


def test_smoke_refuses_to_run_without_a_tpu():
    r = _run([SMOKE])
    assert r.returncode not in (0, None)
    assert "platform 'cpu'" in r.stderr         # names what it found
    assert '"ok"' not in r.stdout and "train" not in r.stdout


def test_smoke_cannot_run_alone(tmp_path):
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py"), "--rehearse-cpu", "2000"],
             cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_smoke_rehearsal_is_green_and_cannot_pass():
    """20k rows, 4 virtual devices (conftest's XLA_FLAGS reach the
    child): every stage incl. the mesh stage runs; no pass line."""
    r = _run([SMOKE, "--rehearse-cpu", "20000", "--chips", "4"])
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert lines and all(ln.startswith("[REHEARSAL") for ln in lines)
    for stage in ("device", "train", "kernel", "predict", "serve",
                  "mesh"):
        assert any(f"{stage}: PASSED" in ln for ln in lines), stage
    assert '"ok"' not in r.stdout
    assert "NOT a pass" in lines[-1]
