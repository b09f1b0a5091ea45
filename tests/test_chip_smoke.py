"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases run end to end on the CPU at a tiny size (the rehearsal of
every chip call)."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shrink(mod):
    mod.SECONDS = 3.0
    mod.CHECK_SHAPE = (8, 8)
    mod.DEPLOY_SHAPE = (16, 8)
    mod.MESH_SHAPE = (8, 8)


@pytest.mark.parametrize("where", ["cpu", "script_alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """No accelerator, or no repo beside the script: non-zero exit and
    no result line."""
    script = SCRIPT
    if where == "script_alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=300, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_on_cpu(dial_model, capsys):
    cs = _load()
    _shrink(cs)
    cs.catalog_phase(dial_model)
    cs.deployment_phase(dial_model)
    text = capsys.readouterr().out
    assert text.count("θ trajectory exact") == 3
    assert "catalog: 12 scenarios" in text
    assert "deployment (smoke run, not a benchmark): 16 clients" in text


def test_chip_smoke_four_chip_phase_on_forced_devices():
    """The mesh phase on four forced host devices, model trained from the
    seed through the campaign path, in a child process."""
    code = f"""
import sys
sys.path.insert(0, {os.path.join(REPO, "src")!r})
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.SECONDS = 3.0
cs.MESH_SHAPE = (8, 8)
cs.four_chip_phase(cs.train(0), 0)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env["XLA_FLAGS"] = " ".join(
        f for f in (env.get("XLA_FLAGS", ""),
                    "--xla_force_host_platform_device_count=4") if f)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "θ bit-equal to one chip" in out.stdout
    assert "no collective in the compiled program" in out.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_location(tmp_path, from_env):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (and no other path), else the one git-ignored in-checkout path."""
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
