"""Fault tolerance for long-running multi-host training.

Three layers, all exercised by tests:

  * ``Heartbeat`` — a watchdog thread that marks the process wedged if the
    training loop stops reporting progress (straggler/deadlock detection).
    On a real cluster the coordinator consumes these beats; here the
    watchdog fires a callback that the loop turns into a checkpoint+abort.
  * ``retrying`` — wraps the device-side step; transient failures
    (preempted TPU, ICI link flap → ``XlaRuntimeError``) trigger
    re-initialization from the last checkpoint instead of killing the job.
  * **elastic restart** — on resume the checkpoint is mesh-independent
    (see checkpoint/manager.py), so a job that lost a pod restarts on a
    smaller mesh by just passing different shardings to ``restore``.

Straggler mitigation at step granularity: the loop records an EMA of step
times; steps slower than ``straggler_factor``× the EMA are logged with the
host id so the coordinator can evict the slow host. (With synchronous SPMD
collectives, evict-and-reshard is the only real mitigation; there is no
per-device work stealing inside a pjit step.)
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional


class Heartbeat:
    def __init__(self, timeout_s: float, on_stall: Callable[[], None]):
        self.timeout_s = timeout_s
        self.on_stall = on_stall
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self.stalled = False

    def start(self):
        self._thread.start()
        return self

    def beat(self):
        self._last = time.monotonic()

    def stop(self):
        self._stop.set()

    def _watch(self):
        while not self._stop.wait(self.timeout_s / 4):
            if time.monotonic() - self._last > self.timeout_s:
                self.stalled = True
                try:
                    self.on_stall()
                finally:
                    return


class StragglerMonitor:
    def __init__(self, factor: float = 3.0, ema: float = 0.9):
        self.factor = factor
        self.ema_coef = ema
        self.ema: Optional[float] = None
        self.events: list[tuple[int, float, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        """Returns True if this step was a straggler."""
        if self.ema is None:
            self.ema = dt
            return False
        slow = dt > self.factor * self.ema
        if slow:
            self.events.append((step, dt, self.ema))
        self.ema = self.ema_coef * self.ema + (1 - self.ema_coef) * dt
        return slow


def retrying(fn: Callable, *, retries: int = 2,
             on_failure: Optional[Callable[[int, Exception], None]] = None,
             retriable: tuple = ()):
    """Retry a step function on transient runtime errors.

    ``retriable`` defaults to jax runtime errors; ``on_failure(attempt, e)``
    is the hook where the loop restores from checkpoint."""
    if not retriable:
        from jax.errors import JaxRuntimeError
        retriable = (JaxRuntimeError,)

    def wrapped(*a, **kw):
        for attempt in range(retries + 1):
            try:
                return fn(*a, **kw)
            except retriable as e:  # pragma: no cover - exercised via mock
                if attempt == retries:
                    raise
                if on_failure is not None:
                    on_failure(attempt, e)
        raise AssertionError("unreachable")

    return wrapped
