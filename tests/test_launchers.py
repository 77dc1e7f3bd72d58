"""CLI smoke tests for the production launchers (subprocess, reduced cfgs)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(args, timeout=420):
    return subprocess.run([sys.executable, "-m"] + args, env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli_dense(tmp_path):
    p = _run(["repro.launch.train", "--arch", "llama3_8b", "--smoke",
              "--steps", "3", "--batch", "2", "--seq", "32",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "uplink compression" in p.stdout
    assert any(f.startswith("ckpt_") for f in os.listdir(tmp_path))


def test_train_cli_audio():
    p = _run(["repro.launch.train", "--arch", "musicgen_large", "--smoke",
              "--steps", "2", "--batch", "2", "--seq", "16"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "done" in p.stdout


def test_serve_cli_ssm():
    p = _run(["repro.launch.serve", "--arch", "mamba2_1p3b", "--smoke",
              "--batch", "2", "--prompt-len", "16", "--gen", "3"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "decode:" in p.stdout


# ---------------------------------------------------------------------------
# compile-cache helper (in-process; jax.config.update is intercepted so the
# worker's JAX config stays untouched)
# ---------------------------------------------------------------------------

def _recorded_updates(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_leaves_env_dir_alone(monkeypatch, tmp_path):
    from repro.launch import cache
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert cache.compile_cache_dir() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from repro.launch import cache
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert [cache.enable_compile_cache() for _ in range(2)] == [expected] * 2
    assert calls == [("jax_compilation_cache_dir", expected)] * 2


def test_device_peaks_keyed_by_device_kind():
    from repro.launch.mesh import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert v5e.flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
