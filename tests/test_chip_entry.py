"""Entry-point guards for chip runs: chip_smoke.py refuses to run without a
TPU, and the compile-cache helper picks its directory as documented."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr and "'cpu'" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = compile_cache.enable()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == str(ROOT / ".jax_cache") == now
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    else:
        # JAX reads the variable itself: the helper sets nothing
        assert got == str(tmp_path / env_dir) and now == before
