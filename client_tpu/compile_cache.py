"""Where JAX's persistent compilation cache lives.

Every cold start on a throw-away machine recompiles prefill, each decode
bucket and the tensor models; the persistent cache turns the second start
into disk reads. The directory is part of the cache key, so it must not
move between runs: an operator (or the chip tool) places it from outside
with ``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable itself and
this module then sets nothing — and otherwise it is ``<checkout>/.jax_cache``,
a fixed path with no tmp name, pid or time in it.

Entry points call :func:`enable_compile_cache` once, before their first
compilation: ``python -m client_tpu.server``, ``client_tpu.pod.worker``
and the pytest TPU tier (``tests/conftest.py``).
"""

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """The directory the cache uses (importable without JAX, so a
    launcher that must stay off the chip can inspect it)."""
    return os.environ.get(ENV_CACHE_DIR) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir` and
    return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_CACHE_DIR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
