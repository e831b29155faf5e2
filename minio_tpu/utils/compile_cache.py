"""JAX's persistent compilation cache, placed once for every launcher.

Every process that jits calls ``configure()`` before its first jit.
The server entry does (``python -m minio_tpu server``, ``__main__.main``),
which is how ``chip_smoke.py``, itself off JAX, and the benchmark's
serving child get it:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set in code — whoever placed the cache from outside owns
  it;
- where it is not, one FIXED directory inside the checkout
  (``<checkout>/.jax_compile_cache``, git-ignored) is used: the path is
  part of the cache key's world, so a directory that moves (``$HOME``,
  a temp name, a pid, a time) never hits.

``configure()`` also hooks JAX's monitoring events into metrics v2 so
the serving process can say how many programs it requested and how
many of those the persistent cache answered:
``minio_tpu_v2_jit_programs_total{result="requested"|"cache_hit"}``
(real compilations = requested - cache_hit).
"""

from __future__ import annotations

import os
import threading

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")

_mu = threading.Lock()
_configured: str | None = None


def configure() -> str:
    """Place the compile cache (idempotent); returns the directory in
    force."""
    global _configured
    with _mu:
        if _configured is not None:
            return _configured
        import jax
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env:
            cache_dir = env
        else:
            cache_dir = DEFAULT_DIR
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            # The data plane's kernels compile in well under a second
            # each; JAX's default floor (1 s) would persist none.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
        _count_programs()
        _configured = cache_dir
        return cache_dir


def _count_programs() -> None:
    from jax import monitoring

    from ..obs.metrics2 import METRICS2

    def on_duration(event: str, _secs: float, **_kw) -> None:
        # Fires once per program handed to the backend, whether the
        # executable then came from the persistent cache or a compile.
        if event == "/jax/core/compile/backend_compile_duration":
            METRICS2.inc("minio_tpu_v2_jit_programs_total",
                         {"result": "requested"})

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            METRICS2.inc("minio_tpu_v2_jit_programs_total",
                         {"result": "cache_hit"})

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
