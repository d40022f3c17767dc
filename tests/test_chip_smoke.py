"""``chip_smoke.py``: the refusal without a TPU, its serve phase at a tiny
size on the CPU (Pallas in interpret mode), and the compile-cache
helper it shares with ``benchmarks.run``."""

import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout == ""              # no result line, no partial report


def test_serve_phase_runs_and_checks_at_tiny_size():
    from repro.configs.qwen1_5_4b import smoke_config

    cfg = dataclasses.replace(smoke_config(), dtype="bfloat16")
    r = _chip_smoke().serve_smoke(
        cfg, seed=0, prompt_lo=4, prompt_hi=24, new_tokens=4,
        buckets=(16, 24), calib_shape=(2, 16), timed_steps=1)
    assert r["failures"] == []
    assert r["requests"] == 8 and r["tokens_out"] == 8 * 4
    assert r["built"] == r["site_classes"] == [("linear", 1, 7, 8, None,
                                                None)]
    assert r["lsb_dev_max"] <= 1e-3      # same codes on the CPU
    assert r["decode_step_ms"] > 0


def test_compile_cache_honours_env_and_defaults_in_checkout(monkeypatch):
    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("mode", ["off", "oracle"])
def test_serve_phase_reports_a_runtime_that_skips_the_kernel(
        monkeypatch, mode):
    """A runtime whose pack takes the composed chain (``off``) or the jnp
    mirror (``oracle``) builds no fused kernel; the smoke's own
    fused-vs-composed comparisons, which do build one, must not hide
    that."""
    import repro.serve as serve
    from repro.configs.qwen1_5_4b import smoke_config
    from repro.sweep.serve_eval import pack_with_fused

    class Unfused(serve.ServeRuntime):
        def __init__(self, cfg, params, *, pack, **kw):
            super().__init__(cfg, params, pack=pack_with_fused(pack, mode),
                             **kw)

    monkeypatch.setattr(serve, "ServeRuntime", Unfused)
    cfg = dataclasses.replace(smoke_config(), dtype="bfloat16")
    r = _chip_smoke().serve_smoke(
        cfg, seed=0, prompt_lo=4, prompt_hi=24, new_tokens=4,
        buckets=(16, 24), calib_shape=(2, 16), timed_steps=1)
    assert r["built"] == []
    assert any("refused the fused kernel" in f for f in r["failures"])
