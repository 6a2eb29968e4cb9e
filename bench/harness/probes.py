"""Spans the benchmark records around calls into the program's layers,
for the traced run only: each GBT fit program with its shape and the chip
it was dispatched to.

They wrap the program's callables from outside and put them back after
the window; the program itself records no spans yet."""

from __future__ import annotations

import threading
import time


class Probes:
    def __init__(self):
        self.lock = threading.Lock()
        self.fits = []           # (start, n, F, n_trees, depth, chip)
        self._undo = []

    def wrap(self, owner, attr: str, record) -> None:
        original = getattr(owner, attr)

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return original(*args, **kwargs)
            finally:
                with self.lock:
                    record(t0, time.perf_counter(), args, kwargs)

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, original))

    def remove(self) -> None:
        for owner, attr, original in reversed(self._undo):
            setattr(owner, attr, original)
        self._undo.clear()


def chip(array) -> int:
    """The ordinal of the chip that holds ``array``, as the trace names
    its plane (``/device:TPU:<ordinal>``)."""
    (device,) = array.devices()
    return device.local_hardware_id


def install() -> Probes:
    from repro.tabular import gbt

    p = Probes()

    def fit(t0, t1, args, kwargs):
        n, F = args[0].shape
        p.fits.append((t0, n, F, args[5] if len(args) > 5
                       else kwargs["n_trees"],
                       args[6] if len(args) > 6 else kwargs["depth"],
                       chip(args[0])))
    p.wrap(gbt, "_fit_jax_binned", fit)
    return p
