"""Process-level JAX set-up for every entry point that compiles
(``recipes/finetune``, ``recipes/serve_model``, ``bench.py``): where
the persistent compile cache lives, and a plain statement of the
device and toolchain the process actually got — so a job log alone
shows whether it ran on the chip.

Nothing here initialises a backend at import; ``device_facts`` and
``runtime_facts`` do when called (their callers own the chip).
"""
import collections
import importlib.metadata
import json
import os
from typing import Any, Dict, Optional

CACHE_DIR_ENV = 'JAX_COMPILATION_CACHE_DIR'
_CACHE_SUBDIR = '.jax_cache'
# Prefix of the one-line device statement; chip_smoke.py finds the
# line in its children's logs by it.
DEVICE_LINE_PREFIX = 'skytpu device '

_EVENT_PREFIX = '/jax/compilation_cache/'
_compile_events: 'collections.Counter[str]' = collections.Counter()
_listener_installed = False


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, resolved from this package's own
    location: jobs launched through the local provider run from a
    runtime dir, so the working directory says nothing about where
    the code lives, and the path must be the same in every process
    and every run for the cache to hit."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, _CACHE_SUBDIR)


def _count_event(event: str, **kwargs) -> None:
    del kwargs
    if event.startswith(_EVENT_PREFIX):
        _compile_events[event[len(_EVENT_PREFIX):]] += 1


def configure_compile_cache() -> str:
    """Turn the persistent compile cache on; call before anything
    compiles. ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads
    it, and no other directory is set anywhere in code. Unset: the
    cache goes to ``default_cache_dir()``. Returns the directory in
    force.

    The entry thresholds are dropped either way: JAX's defaults skip
    executables that compiled in under a second, which is most of
    what a serving replica prewarms (block copies, samplers, small
    prefill buckets) — each is cheap, together they are the time to
    ready."""
    global _listener_installed  # pylint: disable=global-statement
    import jax
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update('jax_compilation_cache_dir',
                          default_cache_dir())
    jax.config.update('jax_persistent_cache_min_compile_time_secs',
                      0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    if not _listener_installed:
        jax.monitoring.register_event_listener(_count_event)
        _listener_installed = True
    return jax.config.jax_compilation_cache_dir


def _libtpu_version() -> Optional[str]:
    try:
        return importlib.metadata.version('libtpu')
    except importlib.metadata.PackageNotFoundError:
        return None


def device_facts() -> Dict[str, Any]:
    """The device as JAX reports it, plus the toolchain versions.
    Initialises the backend; raises whatever JAX raises when the
    platform named by ``JAX_PLATFORMS`` cannot start."""
    import jax
    import jaxlib
    devices = jax.devices()
    return {
        'platform': devices[0].platform,
        'device_kind': devices[0].device_kind,
        'device_count': len(devices),
        'jax': jax.__version__,
        'jaxlib': jaxlib.__version__,
        'libtpu': _libtpu_version(),
    }


def device_line(facts: Dict[str, Any]) -> str:
    """``skytpu device {...}`` — the first line both recipes print."""
    return DEVICE_LINE_PREFIX + json.dumps(facts)


def runtime_facts() -> Dict[str, Any]:
    """What this process compiled and what it holds on the device so
    far: compile-cache misses (= executables really compiled) and
    hits, the cache directory, and per-device memory (absent on
    backends without ``memory_stats``, e.g. the CPU)."""
    import jax

    from skypilot_tpu.metrics import device as device_metrics
    return {
        'compiled': _compile_events['cache_misses'],
        'cache_hits': _compile_events['cache_hits'],
        'cache_dir': jax.config.jax_compilation_cache_dir,
        'memory': device_metrics.sample_device_memory(),
    }
