"""chip_smoke.py refuses to run anywhere but on a TPU, and the launchers'
persistent compile cache is placed from outside or at a fixed path."""

import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "script-alone"])
def test_chip_smoke_refuses_without_a_tpu(tmp_path, alone):
    """On the CPU backend, and in a directory holding nothing of the repo
    but the script, the run fails and never prints a result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if alone:
        cwd = str(tmp_path)
        script = shutil.copy(script, cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def _chip_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("margin,moved,tie", [
    (0.01, 0.02, True),     # runner-up overtakes by noise: a tie
    (1.0, 0.6, False),      # both picks moved 0.6 against a 1.0 margin
])
def test_logit_compare_admits_only_tied_flips(margin, moved, tie):
    """A greedy pick that differs passes only where the reference's margin
    between the two picks is no wider than the row's largest difference."""
    cs = _chip_smoke()
    rng = np.random.default_rng(0)
    want = rng.normal(size=(3, 2, 16)).astype(np.float32)
    want[..., 0], want[..., 1] = 5.0, 5.0 - margin
    got = want.copy()
    got[1, 0, 0] -= moved
    got[1, 0, 1] += moved
    assert got[1, 0].argmax() == 1 and want[1, 0].argmax() == 0
    if tie:
        err = cs.compare("t", got, want, bound=1.0)
        assert err == pytest.approx(moved / 5.0)
    else:
        with pytest.raises(RuntimeError, match="not a tie"):
            cs.compare("t", got, want, bound=1.0)


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env", "checkout-default"])
def test_compile_cache_placement(tmp_path, monkeypatch, from_env):
    if from_env:
        want = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = os.path.join(ROOT, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert cli.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_dry_run_adds_host_devices_only_when_asked(monkeypatch):
    """Importing the dry run sets nothing; forcing its 512 host devices
    keeps every flag already set, an explicit device count included."""
    from repro.launch import dryrun
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    dryrun.force_host_devices()
    assert os.environ["XLA_FLAGS"] == (
        "--xla_dump_to=/dev/null "
        "--xla_force_host_platform_device_count=512")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    dryrun.force_host_devices()
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=8"
