"""Where the entry points keep JAX's persistent compile cache
(``repro.launch.compile_cache``)."""
import os

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_use_compile_cache_places_the_cache(monkeypatch, tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the helper sets nothing;
    without it the cache is ``<checkout>/.jax_cache``."""
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_set:
        assert got == str(tmp_path) and after == before
    else:
        assert got == after == os.path.join(REPO, ".jax_cache")


def test_compiles_land_in_the_env_cache(run_sub, tmp_path):
    """With the variable set, an entry point's compiles are written there
    and nowhere in the checkout."""
    out = run_sub(f"""
        import os
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
        had = CACHE_DIR.exists()
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
        print("ENTRIES", len(os.listdir({str(tmp_path)!r})))
        print("CHECKOUT", CACHE_DIR.exists() and not had)
    """, devices=1, timeout=120)
    assert "ENTRIES 0" not in out and "ENTRIES" in out
    assert "CHECKOUT False" in out
