"""The one place that says where XLA's persistent compile cache lives.

A serving node and every benchmark worker start in a fresh process; with
no persistent cache each start pays every compile again (tens of seconds
at 1M documents). The directory is part of the cache key, so it must not
move between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets one (a deployment's volume, the chip tool's directory)
and ``<checkout>/.jax_cache`` otherwise — never a temp name, a pid or a
time. A process pinned to the CPU backend (control nodes, tests) gets no
default directory: its compiles are short, and XLA:CPU logs a page of
machine-feature warnings for every executable it loads from a cache.

The same call counts what the compiler did into ``global_metrics``
(``xla_compiles``, ``compile_cache_hits``, ``compile_cache_misses``), so
``/api/metrics`` of a worker shows whether a restart found its
executables and whether a serving window compiled anything.
"""

from __future__ import annotations

import os

import jax

from tfidf_tpu.utils.metrics import global_metrics

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# jax.monitoring event -> counter. The backend-compile event fires for
# every executable built OR loaded from the persistent cache (it wraps
# the cache lookup) and stays silent on in-memory jit-cache hits, so a
# zero delta over a window means no new program shape was served in it.
_EVENT_COUNTERS = {
    "/jax/core/compile/backend_compile_duration": "xla_compiles",
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
_listening = False


def _on_event(name: str, *_args, **_kw) -> None:
    counter = _EVENT_COUNTERS.get(name)
    if counter is not None:
        global_metrics.inc(counter)


def configure_compile_cache() -> str | None:
    """Point this process (and, through the environment, its children)
    at the persistent compile cache and start counting compiles;
    returns the directory, None where there is none. Entry points call
    it before their first compile. An environment that names a
    directory is left as it is."""
    global _listening
    path = os.environ.get(_ENV_DIR)
    if not path and jax.config.jax_platforms != "cpu":
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.environ[_ENV_DIR] = os.path.join(root, ".jax_cache")
    if path:
        # jax latched the variable when it was imported (above), so
        # the process itself is told through the config; the floors
        # are zeroed because the served path compiles one small
        # program per power-of-two batch bucket, each under jax's 1 s
        # default floor
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    if not _listening:
        # jax.monitoring has no unregister: one pair per process
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    return path
