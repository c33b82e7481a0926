"""Straggler and liveness watch for long sweeps (the sweep's CellWatchdog).

* StepTimer — rolling step-time stats; flags straggler steps (z-score over a
  window).
* Heartbeat — liveness file other processes can watch.
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Optional


class StepTimer:
    def __init__(self, window: int = 50, z_thresh: float = 3.0):
        self.window = deque(maxlen=window)
        self.z_thresh = z_thresh
        self.stragglers = 0
        self._t0: Optional[float] = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self) -> tuple[float, bool]:
        dt = time.perf_counter() - self._t0
        is_straggler = False
        if len(self.window) >= 10:
            mean = sum(self.window) / len(self.window)
            var = sum((x - mean) ** 2 for x in self.window) / len(self.window)
            std = max(var ** 0.5, 1e-9)
            if (dt - mean) / std > self.z_thresh:
                is_straggler = True
                self.stragglers += 1
        self.window.append(dt)
        return dt, is_straggler


class Heartbeat:
    """Liveness file other processes / the coordinator can watch.

    Callers that share a machine must use a per-process path (the sweep
    derives one from the pid in the tmpdir) — a fixed filename aliases
    concurrent runs and fools the watcher — and must `stop()` when done
    so a stale file never impersonates a live process."""

    def __init__(self, path: str, interval: float = 10.0):
        self.path = path
        self.interval = interval
        self._last = 0.0

    def beat(self, step: int):
        now = time.time()
        if now - self._last >= self.interval:
            with open(self.path, "w") as f:
                f.write(f"{step} {now}\n")
            self._last = now

    def stop(self):
        """Remove the liveness file (idempotent)."""
        self._last = 0.0
        try:
            os.remove(self.path)
        except OSError:
            pass
