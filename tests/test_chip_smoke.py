"""chip_smoke.py's contract, as far as the CPU can hold it to it, and the
no-fallback rules of the device layer it stands on."""
import json
import os
import subprocess
import sys

import pytest

import jax

import paddle_tpu as paddle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the script sets its own device count
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("chips,phases", [
    ("1", ["device", "flash", "serve", "train"]),
    ("4", ["device", "mesh.single", "mesh.dp4_zero1", "mesh.dp2_tp2", "mesh"]),
])
def test_rehearsal_runs_every_phase_and_says_so(chips, phases):
    proc = _run_smoke("--rehearse", "--chips", chips)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [line["phase"] for line in lines[:-1]] == phases
    assert all(line["rehearsal"] is True for line in lines)
    assert all({"wall_s", "compile_s"} <= set(line["setup"])
               for line in lines[:-1] if "." not in line["phase"])
    last = lines[-1]
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": int(chips)}


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--rehearse" in proc.stderr


@pytest.mark.parametrize("name,why", [
    ("tpu", "no 'tpu' devices"),            # tests run on the CPU
    ("gpu", "no 'tpu' devices"),            # reference spelling, same ask
    ("cpu:99", "out of range"),
])
def test_set_device_raises_instead_of_handing_back_another_device(name, why):
    with pytest.raises(ValueError, match=why):
        paddle.set_device(name)


def test_get_device_reports_the_real_platform():
    assert paddle.device.get_device() == "cpu"


@pytest.mark.parametrize("preset", [None, "/some/dir"])
def test_compile_cache_is_placed_from_outside_or_under_the_checkout(
        preset, monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    if preset is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset)
    try:
        got = paddle.device.enable_compile_cache()
        if preset is None:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # jax reads the variable itself: nothing is set in code
            assert got == preset
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
