"""Span tracer: nestable, thread-safe, monotonic-clock context managers.

The spans half of the observability layer (``obs/metrics.py`` is the
metrics half). Call sites write::

    from photon_ml_tpu.obs import trace
    with trace.span("cd.update", coordinate=cid, sweep=it):
        ...

and pay essentially nothing when tracing is disabled (the module-level
``span()`` returns a shared no-op singleton) and two
``time.perf_counter_ns`` reads plus one locked append when enabled —
no jax import, no device work, so instrumented hot loops keep their
sync-discipline contract (tests/test_obs.py proves a traced CD sweep
survives ``jax.transfer_guard_device_to_host("disallow")``).

The store is a ring: a :class:`Tracer` keeps the **newest**
``max_buffered_spans`` closed spans and counts every older one it had to
let go on ``spans_dropped``, so a tracer nobody drains holds a fixed
amount of host memory however long it records.

Export formats:

- **Chrome trace-event JSON** (:meth:`Tracer.chrome_trace` /
  :meth:`Tracer.write_chrome_trace`): complete ``"ph": "X"`` events with
  microsecond ``ts``/``dur`` — loadable in Perfetto / ``chrome://tracing``
  as-is; nesting is implied by timestamp containment per ``tid``.
- **Structured JSONL** (:meth:`Tracer.write_spans_jsonl`): one span per
  line with ``name``/``ts_us``/``dur_us``/``tid``/``depth``/labels, for
  ad-hoc ``jq``/pandas analysis and ``tools/trace_report.py``.

Per-thread nesting depth comes from a ``threading.local`` span stack; the
stack snapshots also feed the heartbeat's stall report (which spans are
currently open when nothing has closed for too long).

**Armed** (:func:`arm`, switched by ``obs.compile.arm()`` /
``--device-telemetry``; every benchmark run arms) means two things, and
nothing else:

- spans are *mirrored* into the profiler's trace
  (:func:`mirror_to_profiler`): a span also enters a
  ``jax.profiler.TraceAnnotation(name, **labels)``, so it lands on the
  host plane of any ``jax.profiler`` capture, on the profiler's own clock
  beside the device's operations, its labels as the event's stats. Outside
  a capture the annotation is a microsecond of native code. Hand-timed
  :func:`record_span` spans are not mirrored (an annotation cannot be
  back-dated);
- spans are *kept*: where no tracer is installed, arming installs one
  that holds the newest :data:`ARMED_MAX_BUFFERED_SPANS`, so that a reader
  in the same process can take a window's durations after the window
  (``benchmark/readers/span_time.py``). An ``ObservedRun`` installs its own
  tracer over it and keeps it: there is one store either way, whatever
  :func:`get_tracer` returns.

Armed switches on no other host work: what samples or exports because a
run is *observed* (``--trace-dir``) asks ``obs/run.py`` for it, never
"is there a tracer".
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional


class _NullSpan:
    """Shared no-op span for the tracing-disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def label(self, **labels) -> None:
        pass


_NULL_SPAN = _NullSpan()

#: ``jax.profiler.TraceAnnotation`` while mirroring is on, else None (this
#: module imports no jax until something asks for the mirror).
_annotation = None


class _Span:
    """A live span: recorded by ``tracer`` (None: mirror only) and, while
    mirroring is on, entered as a profiler annotation too."""

    __slots__ = ("_tracer", "_name", "_labels", "_start_ns", "_depth",
                 "_mirror")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 labels: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._labels = labels or None
        self._mirror = None

    def label(self, **labels) -> None:
        """Add labels known only once the span's work is done (a compile's
        seconds and cost). They reach the tracer's record; the profiler's
        event keeps the labels the span was opened with."""
        self._labels = dict(self._labels or {}, **labels)

    def __enter__(self):
        if self._tracer is not None:
            stack = self._tracer._stack()
            self._depth = len(stack)
            self._start_ns = time.perf_counter_ns()
            # (name, start_ns): the open-span report needs per-span ages
            # to make a stalled run diagnosable from the log alone
            stack.append((self._name, self._start_ns))
        ann = _annotation
        if ann is not None:
            # the native event starts when the annotation is built
            self._mirror = ann(self._name, **(self._labels or {}))
            self._mirror.__enter__()
        return self

    def __exit__(self, *exc):
        if self._mirror is not None:
            self._mirror.__exit__(*exc)
        if self._tracer is not None:
            end_ns = time.perf_counter_ns()
            self._tracer._stack().pop()
            self._tracer._record(self._name, self._start_ns, end_ns,
                                 self._depth, self._labels)
        return False


#: Buffer backstop for a tracer nobody drains (tests, ad-hoc
#: ``trace.enable()``): past this many buffered spans the oldest are let
#: go (and counted on ``spans_dropped``) instead of growing host RAM
#: without bound. An ObservedRun never gets near it — its heartbeat
#: drains the buffer into ``spans.jsonl`` every few seconds.
DEFAULT_MAX_BUFFERED_SPANS = 1_000_000

#: What the tracer that :func:`arm` installs keeps: a benchmark window is
#: a few thousand spans (PERF.md, "what recording costs armed"), a span
#: with its labels some 300 bytes, so an armed process that never drains
#: holds 20 MB of them at the most.
ARMED_MAX_BUFFERED_SPANS = 1 << 16


class Tracer:
    """Collects closed spans as (name, tid, depth, start_ns, dur_ns,
    labels) tuples relative to the tracer's monotonic epoch."""

    def __init__(self, process_index: int = 0,
                 max_buffered_spans: int = DEFAULT_MAX_BUFFERED_SPANS):
        self.process_index = process_index
        self.max_buffered_spans = max_buffered_spans
        self._t0_ns = time.perf_counter_ns()
        self.start_unix = time.time()
        self._lock = threading.Lock()
        # the newest max_buffered_spans closed spans, oldest first
        self._events: collections.deque = collections.deque(
            maxlen=max_buffered_spans)
        self._local = threading.local()
        # thread id -> that thread's live span stack (mutated only by its
        # owner; read racily by the heartbeat for stall reporting)
        self._stacks: dict[int, list[str]] = {}
        self.spans_closed = 0
        self.spans_dropped = 0
        self._last_close_ns = self._t0_ns

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            with self._lock:
                self._stacks[threading.get_ident()] = stack
        return stack

    def span(self, name: str, **labels) -> _Span:
        return _Span(self, name, labels)

    def record_span(self, name: str, start_ns: int, end_ns: int,
                    depth: int = 0, labels: Optional[dict] = None) -> None:
        """Record an already-timed span from explicit
        ``time.perf_counter_ns`` timestamps (same clock as the context
        manager, so recorded and live spans share one timeline).

        The serve plane's request spans are timed by hand — the start
        (admission, enqueue) and the end (reply) happen on different
        threads, so a context manager can't bracket them. Cross-process
        request linkage rides on ``labels``: ``trace_id``/``span_id``/
        ``parent`` labels stitch the trees back together in
        ``tools/trace_merge.py`` / ``obs/otlp.py``."""
        self._record(name, start_ns, end_ns, depth, labels or None)

    def _record(self, name, start_ns, end_ns, depth, labels) -> None:
        event = (name, threading.get_ident(), depth,
                 start_ns - self._t0_ns, end_ns - start_ns, labels)
        with self._lock:
            if len(self._events) == self.max_buffered_spans:
                self.spans_dropped += 1  # the append lets the oldest go
            self._events.append(event)
            self.spans_closed += 1
            self._last_close_ns = end_ns

    # -- heartbeat hooks ---------------------------------------------------

    def seconds_since_last_close(self) -> float:
        """Monotonic seconds since the last span closed (since the tracer
        started if none has) — the heartbeat's stall signal."""
        return (time.perf_counter_ns() - self._last_close_ns) / 1e9

    def open_spans(self) -> list[str]:
        """Currently open span names across all threads, outermost
        first (best-effort snapshot for stall reporting)."""
        with self._lock:
            stacks = list(self._stacks.values())
        out: list[str] = []
        for stack in stacks:
            out.extend(name for name, _ in list(stack))
        return out

    def open_span_report(self) -> list[str]:
        """Per-thread open-span stacks WITH per-span ages, outermost
        first — the postmortem the heartbeat dumps into the driver log
        on a stall episode, so a hung run is diagnosable from the log
        alone (which span is wedged, and for how long)."""
        now = time.perf_counter_ns()
        with self._lock:
            stacks = list(self._stacks.items())
        lines: list[str] = []
        for tid, stack in stacks:
            snap = list(stack)
            if not snap:
                continue
            chain = " > ".join(f"{name} (open {(now - start) / 1e9:.1f}s)"
                               for name, start in snap)
            lines.append(f"thread {tid}: {chain}")
        return lines

    def uptime_seconds(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e9

    def rel_ts_us(self, ns: int) -> float:
        """Tracer-epoch-relative microseconds for a ``perf_counter_ns``
        stamp — the ``ts_us`` convention of :meth:`events`, so records
        built outside the tracer (the serve exemplar reservoir) land on
        the same timeline as drained spans."""
        return (ns - self._t0_ns) / 1e3

    # -- export ------------------------------------------------------------

    @staticmethod
    def _as_dicts(snapshot: list[tuple]) -> list[dict]:
        return [{"name": name, "tid": tid, "depth": depth,
                 "ts_us": start_ns / 1e3, "dur_us": dur_ns / 1e3,
                 "labels": labels or {}}
                for name, tid, depth, start_ns, dur_ns, labels in snapshot]

    def events(self) -> list[dict]:
        """Closed spans as dicts (ts/dur in microseconds)."""
        with self._lock:
            snapshot = list(self._events)
        return self._as_dicts(snapshot)

    def drain(self) -> list[dict]:
        """Remove and return the buffered spans (same dicts as
        :meth:`events`). The ObservedRun's heartbeat spills these into
        ``spans.jsonl`` so a long run's buffer stays bounded and a
        killed run keeps every span spilled so far."""
        with self._lock:
            snapshot = list(self._events)
            self._events.clear()
        return self._as_dicts(snapshot)

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing)."""
        return chrome_document(self.events(), self.process_index,
                               self.start_unix)

    def write_chrome_trace(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)

    def write_spans_jsonl(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            for e in self.events():
                fh.write(json.dumps(e) + "\n")


def chrome_document(events: list[dict], process_index: int,
                    start_unix: float) -> dict:
    """Chrome trace-event JSON document from :meth:`Tracer.events`-shaped
    dicts — shared by the in-memory export above and the ObservedRun,
    which rebuilds ``trace.json`` from the spilled ``spans.jsonl``."""
    out = [{"name": e["name"], "cat": "photon", "ph": "X",
            "ts": e["ts_us"], "dur": e["dur_us"],
            "pid": process_index, "tid": e["tid"],
            "args": e.get("labels") or {}}
           for e in events]
    out.sort(key=lambda ev: ev["ts"])
    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {
            "process_index": process_index,
            "start_unix_time": start_unix,
        },
    }


#: Process-global tracer; None = tracing disabled (the default).
_tracer: Optional[Tracer] = None

#: The tracer :func:`arm` installed (None where it found one installed).
_armed_tracer: Optional[Tracer] = None


def enable(process_index: int = 0) -> Tracer:
    """Install (and return) a fresh process-global tracer (over the one
    arming installed, where there is one)."""
    global _tracer
    _tracer = Tracer(process_index=process_index)
    return _tracer


def disable() -> None:
    global _tracer
    _tracer = None


def get_tracer() -> Optional[Tracer]:
    return _tracer


def arm(on: bool) -> None:
    """Armed: spans are mirrored into ``jax.profiler`` captures and kept
    (see the module docstring). ``obs.compile.arm()`` / ``disarm()`` call
    this: there is one switch for the device plane, not two. Disarming
    takes away only the tracer that arming installed."""
    global _tracer, _armed_tracer
    mirror_to_profiler(on)
    if on:
        if _tracer is None:
            _tracer = _armed_tracer = Tracer(
                max_buffered_spans=ARMED_MAX_BUFFERED_SPANS)
    else:
        if _tracer is _armed_tracer:
            _tracer = None
        _armed_tracer = None


def mirror_to_profiler(on: bool) -> None:
    """Switch the mirroring of spans into ``jax.profiler`` captures alone
    (:func:`arm` calls this; a test of the mirror calls it directly)."""
    global _annotation
    if on:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    else:
        _annotation = None


def span(name: str, **labels):
    """A span on the global tracer — or the shared no-op when tracing is
    off and nothing mirrors, so call sites never branch."""
    t = _tracer
    if t is None and _annotation is None:
        return _NULL_SPAN
    return _Span(t, name, labels)


def record_span(name: str, start_ns: int, end_ns: int,
                depth: int = 0, **labels) -> None:
    """An explicit-timestamp span on the global tracer (no-op when
    tracing is off) — see :meth:`Tracer.record_span`."""
    t = _tracer
    if t is None:
        return
    t.record_span(name, start_ns, end_ns, depth, labels or None)
