"""Device-mesh helpers, the persistent compilation cache, and short
names for the JAX SPMD primitives the repo uses::

    from repro.compat import shard_map
"""
from __future__ import annotations

import collections
import os
from pathlib import Path
from typing import Optional

import jax
import numpy as np
from jax import lax
from jax.experimental.compilation_cache import compilation_cache as _cc

__all__ = ["shard_map", "axis_size", "pcast", "vma_of",
           "make_auto_mesh", "make_auto_device_mesh", "device_mesh_1d",
           "set_host_device_count", "enable_persistent_compilation_cache",
           "disable_persistent_compilation_cache",
           "compilation_cache_stats", "reset_compilation_cache_stats"]

shard_map = jax.shard_map
axis_size = lax.axis_size
pcast = lax.pcast


def vma_of(x):
    """Varying-manual-axes set of ``x`` inside ``shard_map``."""
    return tuple(jax.typeof(x).vma)


def set_host_device_count(n: int) -> None:
    """Give the process ``n`` CPU devices.  Must run before the first jax
    backend use."""
    jax.config.update("jax_num_cpu_devices", n)


# ----------------------------------------------------------------------
# persistent (on-disk) XLA compilation cache
# ----------------------------------------------------------------------
# where the cache lives when JAX_COMPILATION_CACHE_DIR is not set: a fixed
# path (the directory is part of what a later process must find again)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / "experiments" \
    / "xla_cache"

# counters fed by jax.monitoring events; hits/misses are only recorded by
# jax while a cache dir is configured
_CACHE_EVENTS: collections.Counter = collections.Counter()
_CACHE_LISTENER_REGISTERED = False
_CACHE_DIR: Optional[Path] = None

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _cache_event_listener(event: str, **_kw) -> None:
    if "compilation_cache" in event:
        _CACHE_EVENTS[event] += 1


def enable_persistent_compilation_cache() -> Path:
    """Point JAX's on-disk XLA compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` as given when it is set, otherwise at
    :data:`DEFAULT_CACHE_DIR` (created if missing), so a later process
    compiling an identical program deserializes the executable instead of
    re-running XLA.  The one place the repo arms the cache: JAX's own
    cache key already covers the program, so the directory carries no
    subkey.  The entry-size / compile-time floors are dropped so even
    small programs cache.  Returns the directory; idempotent.

    The cache initializes lazily at the first compile and then latches;
    the ``reset_cache`` here makes arming it after earlier jit calls in
    the process take effect.
    """
    global _CACHE_LISTENER_REGISTERED, _CACHE_DIR
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = Path(env) if env else DEFAULT_CACHE_DIR
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _CACHE_LISTENER_REGISTERED:
        jax.monitoring.register_event_listener(_cache_event_listener)
        # cache *hits* are reported as duration events, not plain ones
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: _cache_event_listener(event))
        _CACHE_LISTENER_REGISTERED = True
    _cc.reset_cache()
    _CACHE_DIR = path
    return path


def disable_persistent_compilation_cache() -> None:
    """Detach the on-disk compilation cache (fresh compiles pay full XLA
    cost again).  Used by benchmarks that need an honest no-cache
    baseline leg; re-enable with
    :func:`enable_persistent_compilation_cache`."""
    global _CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    _CACHE_DIR = None


def compilation_cache_stats() -> dict:
    """Hit/miss/entry accounting of the persistent compilation cache (all
    zero until :func:`enable_persistent_compilation_cache` ran and a jit
    compile exercised it)."""
    entries = 0
    if _CACHE_DIR is not None and _CACHE_DIR.is_dir():
        entries = sum(1 for p in _CACHE_DIR.iterdir() if p.is_file())
    return {
        "enabled": _CACHE_DIR is not None,
        "dir": None if _CACHE_DIR is None else str(_CACHE_DIR),
        "hits": int(_CACHE_EVENTS[_HIT_EVENT]),
        "misses": int(_CACHE_EVENTS[_MISS_EVENT]),
        "entries": entries,
    }


def reset_compilation_cache_stats() -> None:
    """Zero the hit/miss counters (the on-disk entries stay)."""
    _CACHE_EVENTS.clear()


# ----------------------------------------------------------------------
# device meshes
# ----------------------------------------------------------------------
def make_auto_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types)


def make_auto_device_mesh(devices, axis_names):
    """``jax.sharding.Mesh`` over an explicit device array, all axes
    Auto."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.sharding.Mesh(devices, axis_names, axis_types=axis_types)


def device_mesh_1d(n: int, axis_name: str = "devices"):
    """A 1-D device mesh over the first ``n`` local devices — the
    fan-out axis :func:`shard_map` batch runners (``repro.dse``) shard
    over.  Raises ``ValueError`` when ``n`` exceeds the devices actually
    present."""
    devices = jax.devices()
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"device_mesh_1d needs 1 <= n <= {len(devices)} available "
            f"devices, got n={n}")
    return make_auto_device_mesh(np.asarray(devices[:n]), (axis_name,))
