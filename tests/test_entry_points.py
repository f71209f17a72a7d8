"""Entry-point contracts: the compile-cache location and ``chip_smoke.py``.

``chip_smoke.py`` is the program's proof that it runs on a TPU. Without one
it must refuse, and never print its result line; its CPU rehearsal runs
every check at reduced widths through ``launch.train.main`` and
``launch.serve.main`` and still ends without a result.
"""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(args, cwd, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_var_stands(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_defaults_to_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_chip_smoke_refuses_without_tpu(args):
    r = _run_smoke(args, ROOT)
    assert r.returncode == 2, r.stderr[-2000:]
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("args", [[], ["--cpu-rehearsal"]])
def test_chip_smoke_fails_without_the_repo(tmp_path, args):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run_smoke(args, tmp_path)
    assert r.returncode not in (0, 3), r.stdout[-2000:]
    assert '"ok"' not in r.stdout


def test_chip_smoke_cpu_rehearsal_runs_every_check(tmp_path):
    r = _run_smoke(["--cpu-rehearsal"], ROOT,
                   {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 3, (r.stdout[-3000:], r.stderr[-3000:])
    out = r.stdout
    assert "FAIL" not in out and '"ok"' not in out
    for phase in ("train_scan", "train_host", "train_fused", "serve"):
        assert f"[{phase}] set-up time" in out
    passed = [ln for ln in out.splitlines() if ln.strip().startswith("PASS")]
    for check in ("scan engine vs HostRoundEngine", "fused Pallas decode",
                  "zero recompiles after warmup",
                  "match ScanEvalEngine on the same bank"):
        assert any(check in ln for ln in passed), check
    assert "all phases passed" in out
