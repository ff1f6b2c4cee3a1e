"""Where the persistent XLA compilation cache lives — decided in ONE place.

``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself at import, so this
module does nothing and sets no other directory.  Unset: the cache goes
to ``<checkout>/.jax_cache`` (ignored by git), a fixed path so repeat
runs in the same checkout hit it.  Every entry point that compiles
(``engine.train``, ``cli.main``, the serving ``ModelServer``,
``chip_smoke.py``, ``tests/conftest.py``) calls
:func:`enable_persistent_cache` before its first compile.
"""
import os

_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Return the compile-cache directory in use, pointing jax at the
    checkout default first when the environment names none.  Whoever
    enables the cache is about to compile, so the compile counters
    (``obs/counters.install_compile_listener``) start listening here."""
    from ..obs.counters import install_compile_listener
    install_compile_listener()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
