"""What keys on the platform or the environment outside the program:
where the compile cache goes."""
import jax
import pytest

from repro import compile_cache


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, from_env):
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable() == str(tmp_path)
            # JAX reads the variable itself; no other directory is set
            assert (jax.config.jax_compilation_cache_dir
                    == saved["jax_compilation_cache_dir"])
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(compile_cache.DEFAULT_DIR)
            assert compile_cache.enable() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
