"""chip_smoke.py and the device-selection layer under it (config.py):
the smoke refuses to run without a TPU, its CPU rehearsal drives every
stage, the compile cache can be placed from outside, and the TPU test
does not swallow a backend that fails to initialise."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **env):
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        # One CPU device: the suite's eight virtual ones only slow it.
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="", **env),
    )


def test_without_a_tpu_it_exits_nonzero_and_trains_nothing():
    out = _run([])
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr and "not 'tpu'" in out.stderr
    assert out.stdout.strip() == ""  # no stage ran, no result line


def test_cpu_rehearsal_runs_every_stage(tmp_path):
    cache = tmp_path / "cache"
    out = _run(
        ["--allow-cpu", "--rows", "20000"],
        JAX_COMPILATION_CACHE_DIR=str(cache),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    stages = [ln.split("]")[0][1:] for ln in lines if ln.startswith("[")]
    assert stages == [
        "device", "train", "histogram", "predict", "deep", "result"
    ]
    # Last on stdout: the contract object, these keys and no others.
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    rec = json.loads(lines[-2].removeprefix("[result] "))
    assert rec["rehearsal"] is True
    assert rec["device_checks"] == "not checked"
    assert rec["histogram_parity"]["counts_equal"] is True
    assert rec["predict"]["checked_engine"] == "QuickScorerEngine"
    assert rec["deep"]["checked_engine"] == "PallasBankEngine"
    assert rec["predict"]["engine_vs_routed"] <= 1e-6
    assert rec["deep"]["engine_vs_routed"] <= 1e-6
    # The cache went where the environment said, and nowhere else.
    assert rec["compile_cache"]["dir"] == str(cache)
    assert rec["compile_cache"]["entries_at_start"] == 0
    assert rec["compile_cache"]["entries_at_end"] == len(os.listdir(cache)) > 0


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from ydf_tpu import config

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: calls.append(a)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert config.enable_compile_cache() == "/some/dir"
    assert calls == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    here = os.path.join(REPO, ".jax_cache")
    assert config.enable_compile_cache() == here
    assert calls == [("jax_compilation_cache_dir", here)]


def test_is_tpu_backend_does_not_swallow_a_failing_backend(monkeypatch):
    import jax

    from ydf_tpu.config import is_tpu_backend

    assert is_tpu_backend() is False  # the suite runs on the CPU

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        is_tpu_backend()
