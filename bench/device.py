"""Device stamp, XLA compile counter and device memory reads.

Copied from ``chip_smoke.py`` (the stamp, the compile counter from
``jax.monitoring``, the ``memory_stats`` read) so that the yardstick
does not move when the smoke script does.
"""
from __future__ import annotations

import threading


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def device_stamp(n_chips: int) -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices.
    Raises :class:`NoAccelerator` off a TPU or with fewer chips than the
    cell asks for: the benchmark never falls back to the CPU."""
    import jax

    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if stamp["platform"] != "tpu":
        raise NoAccelerator(f"no TPU: jax.devices()[0] is {stamp['platform']!r}")
    if len(devs) < n_chips:
        raise NoAccelerator(f"need {n_chips} chips, JAX reports {len(devs)}")
    return stamp


class CompileCounter:
    """Counts XLA backend compiles from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration_secs, **_):
        if event == self.EVENT:
            with self._lock:
                self.count += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend does not report it, as the CPU does not)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


class HostCpu:
    """Host CPU time over a stretch of wall time, from ``/proc/stat``:
    the share of every core's time that was busy, the share that the
    hypervisor stole, and this process's CPU seconds.  A slow run whose
    host was short of cores shows it here.  Empty off Linux."""

    def __init__(self):
        import time

        self._t = time.process_time()
        self._stat = self._read()

    @staticmethod
    def _read():
        try:
            with open("/proc/stat") as f:
                cols = [int(x) for x in f.readline().split()[1:]]
        except (OSError, ValueError):
            return None
        # user nice system idle iowait irq softirq steal ...
        return cols

    def reading(self) -> dict:
        import time

        out = {"process_cpu_s": time.process_time() - self._t}
        end = self._read()
        if self._stat and end:
            d = [b - a for a, b in zip(self._stat, end)]
            total = sum(d[:8]) or 1
            out.update(busy_share=1.0 - (d[3] + d[4]) / total,
                       steal_share=d[7] / total if len(d) > 7 else 0.0)
        return out
