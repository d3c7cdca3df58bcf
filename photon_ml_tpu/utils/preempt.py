"""Cooperative preemption: the stop flag and every source that sets it.

Real fleets rarely kill a trainer outright — they SIGTERM it with a
grace window (preemption), or enforce a wall-clock quota, or ask it to
step aside via an out-of-band file. All three reduce to the same
contract here: a :class:`StopController` owns one sticky stop flag, and
``run_coordinate_descent`` polls it ONLY at commit barriers (raw block
boundaries — the same places snapshots are legal), resolves any
in-flight pipelined handle, takes a final snapshot, and raises
:class:`PreemptionRequested`. The driver turns that into a
``PHOTON_PREEMPTED step=<sweep>.<coord>`` line, ``run_end
{status: "preempted"}``, and the documented requeue exit code
(``cli.PREEMPTED_EXIT``) — and a resume from the final snapshot is
bit-exact vs the uninterrupted run, exactly like crash resume.

Sources, in polling order:

- **explicit** — ``request_stop(reason)``, used by the signal handlers
  (SIGTERM/SIGINT set the flag; a SECOND delivery of the same signal
  restores the previous disposition and re-raises it, so a stuck run
  can still be forced down);
- **deadline** — ``max_train_seconds`` measured on a monotonic clock
  from controller construction (the driver builds it at startup, so
  the budget covers ingest + compile, like a scheduler quota does);
- **stop file** — existence of ``stop_file``, stat'ed at most every
  :data:`STOP_FILE_POLL_SECS` so the hot loop never pays a per-block
  filesystem round trip.

The CD loop accepts ANY object with a ``should_stop() -> str | None``
method — tests drive deterministic stops with a counter fake.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional

# Minimum seconds between stop-file stat() calls: a commit barrier can
# arrive every few milliseconds on small sweeps and the flag is advisory
# anyway — one pending poll per quarter second is plenty responsive.
STOP_FILE_POLL_SECS = 0.25


class PreemptionRequested(Exception):
    """A stop source fired and the CD loop reached a commit barrier:
    the final snapshot (when checkpointing is on) is already written by
    the time this propagates. ``sweep``/``coordinate_index`` name the
    NEXT unit of work — the exact resume point, same convention as the
    snapshot schema's "about to run this coordinate"."""

    def __init__(self, reason: str, sweep: int, coordinate_index: int):
        self.reason = reason
        self.sweep = int(sweep)
        self.coordinate_index = int(coordinate_index)
        super().__init__(
            f"preemption requested ({reason}) at step {self.step}")

    @property
    def step(self) -> str:
        """``<sweep>.<coord>`` — the greppable position format shared
        with fault tags and the ``PHOTON_PREEMPTED`` line."""
        return f"{self.sweep}.{self.coordinate_index}"


class StopController:
    """One sticky stop flag fed by signals, a wall-clock deadline, and
    a cooperative stop file; polled by the training loop at commit
    barriers via :meth:`should_stop`."""

    def __init__(self, max_train_seconds: Optional[float] = None,
                 stop_file: Optional[str] = None,
                 clock=time.monotonic):
        self._clock = clock
        self._event = threading.Event()
        self._reason: Optional[str] = None
        self._lock = threading.Lock()
        self._deadline = (clock() + float(max_train_seconds)
                          if max_train_seconds and max_train_seconds > 0
                          else None)
        self._stop_file = stop_file or None
        self._next_file_poll = clock()  # first poll is free
        self._prev_handlers: dict[int, object] = {}

    # -- flag -----------------------------------------------------------

    def request_stop(self, reason: str) -> None:
        """Latch the flag (first reason wins; later calls are no-ops).
        Safe from signal handlers and other threads."""
        with self._lock:
            if self._reason is None:
                self._reason = reason
        self._event.set()

    @property
    def stop_requested(self) -> bool:
        return self._event.is_set()

    def should_stop(self) -> Optional[str]:
        """The poll the CD loop runs at every commit barrier: returns
        the stop reason, or None to keep training. Checks the latched
        flag first (free), then the deadline (one clock read), then the
        stop file (throttled stat)."""
        if self._event.is_set():
            return self._reason
        now = self._clock()
        if self._deadline is not None and now >= self._deadline:
            self.request_stop("deadline:max_train_seconds")
            return self._reason
        if self._stop_file is not None and now >= self._next_file_poll:
            self._next_file_poll = now + STOP_FILE_POLL_SECS
            if os.path.exists(self._stop_file):
                self.request_stop(f"stop_file:{self._stop_file}")
                return self._reason
        return None

    # -- signals --------------------------------------------------------

    def install_signal_handlers(
            self, signums=(signal.SIGTERM, signal.SIGINT)) -> None:
        """Route SIGTERM/SIGINT into the stop flag. A SECOND delivery of
        the same signal restores the previous disposition and re-raises
        it — the escape hatch when the run never reaches a barrier (the
        supervisor's SIGTERM→grace→SIGKILL ladder relies on kill; an
        operator at a terminal gets the familiar double-Ctrl-C)."""
        for signum in signums:
            self._prev_handlers[signum] = signal.getsignal(signum)
            signal.signal(signum, self._on_signal)

    def _on_signal(self, signum, frame) -> None:
        if self._event.is_set():
            prev = self._prev_handlers.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev)
            os.kill(os.getpid(), signum)
            return
        self.request_stop(f"signal:{signal.Signals(signum).name}")

    def uninstall_signal_handlers(self) -> None:
        """Restore the dispositions saved by
        :meth:`install_signal_handlers` (tests run controllers
        in-process, back to back)."""
        while self._prev_handlers:
            signum, prev = self._prev_handlers.popitem()
            signal.signal(signum, prev)
