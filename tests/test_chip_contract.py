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
    assert kinds == {"solo", "batched", "lanes", "node-stats"}
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
