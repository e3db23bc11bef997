"""chip_smoke.py cannot pass without a chip, and the compile cache can be
placed from outside (ISSUE 21)."""

from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

from megatron_llm_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_exits_nonzero_when_probe_reports_cpu(tmp_path):
    """Its probe child sees a CPU: non-zero, quickly, with the reason, and
    no result line."""
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, 3)
    assert time.monotonic() - t0 < 60
    assert "'cpu', not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout
    assert not [l for l in r.stdout.splitlines() if '"phase"' in l]


def test_chip_smoke_needs_the_repo_beside_it(tmp_path):
    """Alone in a directory it has nothing to drive: non-zero, no result."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 2 and "not a checkout" in r.stderr
    assert r.stdout == ""


def test_cache_dir_from_env_sets_nothing_in_code(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert platform.enable_compilation_cache() == "/somewhere/else"
    assert updates == []


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # this process is pinned to the CPU: no cache is kept there
    assert platform.enable_compilation_cache() is None and updates == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = os.path.join(REPO, ".jax_cache")
    assert platform.enable_compilation_cache() == want
    assert platform.enable_compilation_cache() == want   # never a fresh name
    assert updates == [("jax_compilation_cache_dir", want)] * 2
