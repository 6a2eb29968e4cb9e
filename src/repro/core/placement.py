"""Pinning host threads to one JAX device.

JAX's default device is thread-local: an array made from host data, and a
jitted call whose inputs are all uncommitted, land on the calling thread's
default device.  A service shard that owns one device of a multi-chip host
therefore runs every thread that dispatches for it — its dispatcher, its
executor pool, the runtime's inter-op pool and the background compile
lane — through :func:`on_device`.
"""

from __future__ import annotations

from typing import Any, Callable


def on_device(device: Any, fn: Callable) -> Callable:
    """``fn`` wrapped to run with ``device`` as JAX's default device on
    whichever thread calls it; ``fn`` itself when ``device`` is None."""
    if device is None:
        return fn

    def pinned(*args, **kwargs):
        import jax
        with jax.default_device(device):
            return fn(*args, **kwargs)

    return pinned
