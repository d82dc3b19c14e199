"""The entry points' persistent compilation cache: where it lives."""
import jax

from repro.compile_cache import CACHE_DIR, enable_compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


def test_cache_dir_follows_env_else_fixed_checkout_dir(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache stays there (JAX read
    it at import) and no other directory is set; unset, it goes to the
    one fixed, gitignored directory at the checkout root.  Either way
    sub-second compiles are cached."""
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        root = CACHE_DIR.parent
        assert (root / "chip_smoke.py").exists()
        assert f"/{CACHE_DIR.name}/" in (root / ".gitignore").read_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
