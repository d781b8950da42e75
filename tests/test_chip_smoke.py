"""``chip_smoke.py``'s contract off the chip: it refuses to run without
one, and it keeps the compile cache where ``benchmarks/compile_cache.py``
says. (What it does on the chip only a chip run shows.)
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_chip_smoke_refuses_to_run_without_a_chip():
    """``chip_smoke.py`` with no argument on a machine without a TPU
    exits non-zero before it builds a model and prints no result — the
    driver runs exactly this in the sandbox and it must fail."""
    import subprocess
    import time

    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, None), r.stdout
    assert '"ok"' not in r.stdout and "model:" not in r.stdout
    assert "needs 1 tpu device" in r.stderr
    assert time.monotonic() - t0 < 60


@pytest.mark.parametrize("env_dir", [None, "/some/where/else"])
def test_compile_cache_directory_rule(env_dir, monkeypatch):
    """``chip_smoke.py``'s rule: with JAX_COMPILATION_CACHE_DIR set, no directory is set in code (JAX
    reads the variable itself); unset, the cache is the one fixed
    directory inside the checkout."""
    import jax

    from benchmarks import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == \
            compile_cache.DEFAULT_DIR
        assert updates == [("jax_compilation_cache_dir",
                            compile_cache.DEFAULT_DIR)]
        assert compile_cache.DEFAULT_DIR == os.path.join(
            compile_cache.REPO_ROOT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert updates == []
