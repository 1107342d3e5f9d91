"""The device a run is on: the no-fallback check, the stamp, the peak memory,
and a counter of compilations."""
from __future__ import annotations

import os


def device_doc() -> dict:
    """The device every printed number ran on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_device(chips: int) -> dict:
    """A run that finds no TPU, or fewer chips than the cell asks for, exits
    non-zero and prints no result.  ``JAX_PLATFORMS=cpu`` is the only, explicit,
    way to run off-chip (the CPU tests); such a run prints no device metric."""
    doc = device_doc()
    off_chip = os.environ.get("JAX_PLATFORMS") == "cpu"
    if doc["platform"] != "tpu" and not off_chip:
        raise SystemExit(
            f"fedbench: no TPU attached (jax reports platform "
            f"{doc['platform']!r}); set JAX_PLATFORMS=cpu to run off-chip on "
            f"purpose")
    if doc["count"] < chips:
        raise SystemExit(f"fedbench: the cell needs {chips} chips, jax "
                         f"reports {doc['count']}")
    return doc


def memory_peak_bytes(n_devices: int) -> int:
    """``peak_bytes_in_use`` on the fullest of the first ``n_devices`` devices
    (0 where the backend does not report it, as on the CPU): the buffers the
    program asked for - arguments, results, the resident population - which
    is what a deployment keeps on the chip.

    The TPU runtime books the loaded programs' temporaries apart, as
    ``peak_bytes_reserved`` (PR 22: 1.41 GB in use + 4.30 GB reserved for a
    program whose one temporary is a 4.29 GB relayout copy).  That pool is
    the compiler's, not the workload's - an optimisation that removes the
    copy removes it - so it is not counted here; the detail line prints the
    runtime's whole ``memory_stats()``."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[:n_devices]))


class CompileCounter:
    """Counts XLA compilations (cache hits included: a program that was
    looked up is a program that was not warmed) and their seconds."""

    def __init__(self):
        from jax._src import monitoring
        self.count, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration
