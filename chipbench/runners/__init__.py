"""Runner kinds: ``run(ctx) -> result``, found by the name in the mix's file.

A result has ``correct``, ``attempted``, ``failed``, ``device`` (as the
last line wants it), ``end_to_end`` ({metric: value}), ``records`` (what
the per-layer readers read) and, from a traced run, ``breakdown``.
"""

from __future__ import annotations

import time


def device_entry(devs, peak_bytes: int, trace: dict | None = None) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def peak_of(stats: dict) -> int:
    """One chip's high-water mark: its arrays' (``peak_bytes_in_use``) plus
    what loaded programs hold for their temporaries
    (``peak_bytes_reserved``, which the first does not include: a
    Transformer step whose float32 logits alone are 2.1 GB read 1.08 GB
    in use and 5.74 GB reserved)."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def peak_bytes(devs) -> int:
    """The fullest chip's high-water mark so far (0 where the backend keeps
    no statistics, as the CPU's does not)."""
    return max(peak_of(d.memory_stats() or {}) for d in devs)


class CompileCounter:
    """Counts compile requests heard through ``jax.monitoring``: there
    should be none between ``open()`` and ``close()``."""

    def __init__(self):
        from jax import monitoring

        self.in_window = 0
        self._open = False
        monitoring.register_event_listener(self._heard)

    def _heard(self, event, **_):
        if self._open and event.endswith("compile_requests_use_cache"):
            self.in_window += 1

    def open(self):
        self._open = True

    def close(self):
        self._open = False


now = time.perf_counter
