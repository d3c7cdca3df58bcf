"""Device-memory accounting: HBM gauges, peaks, per-coordinate watermarks.

The second device-plane half of ``--device-telemetry``. Armed, it:

- samples ``device.memory_stats()`` for every local device at heartbeat
  cadence (the ObservedRun's span-spill hook) into the
  ``hbm_bytes{device, kind}`` gauge family — ``bytes_in_use`` /
  ``peak_bytes_in_use`` where the runtime reports them (TPU/GPU), with
  a ``live_bytes`` fallback summed from ``jax.live_arrays()`` metadata
  on backends that don't (CPU), so the gauge family exists everywhere
  the tests run;
- tracks the run-wide peak (:func:`peak_bytes`), which the ObservedRun
  stamps into the ``run_end`` record as ``peak_hbm_bytes`` — the one
  number a capacity reviewer wants from a finished run;
- attributes watermarks per coordinate: the CD commit path calls
  :func:`note_coordinate` after installing a block (metadata-only —
  enumerating live arrays never syncs the device), and the existing
  sweep-boundary drain calls :func:`drain_coordinate_watermarks`,
  emitting a ``hbm_watermark_bytes{coordinate}`` gauge plus one
  ``cd.hbm_watermark`` span per coordinate touched that sweep.

Everything above is gated on :func:`armed` so the un-flagged hot path
pays one module-global check, and jax is imported lazily so ``obs.run``
stays importable on a bare host.

One switch here is not ``--device-telemetry``'s: :func:`watch_sweeps`,
which every observed run (``--trace-dir``, ``obs/run.py``) turns on and
the coordinate-descent loop asks (:func:`sweeps_watched`) before it walks
``jax.live_arrays()`` at a sweep's end. It is a switch of its own because
"a tracer is installed" no longer means "somebody wants samples": a
process that is only armed (a benchmark run) records spans and must run
no other host code for it.
"""

from __future__ import annotations

import threading
from typing import Optional

from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY, MetricsRegistry

_ARMED = False
_SWEEPS_WATCHED = False
_REGISTRY: MetricsRegistry = REGISTRY
_LOCK = threading.Lock()
_PEAK_BYTES = 0
#: coordinate id -> max live bytes observed at any of its commits since
#: the last sweep-boundary drain.
_COORD_WATERMARKS: dict[str, int] = {}

#: memory_stats keys worth exporting (the runtime reports many more;
#: these are the capacity-planning set).
_STAT_KINDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
               "largest_alloc_size")


def arm(registry: Optional[MetricsRegistry] = None) -> None:
    global _ARMED, _REGISTRY, _PEAK_BYTES
    _REGISTRY = registry or REGISTRY
    with _LOCK:
        _PEAK_BYTES = 0
        _COORD_WATERMARKS.clear()
    _ARMED = True


def disarm() -> None:
    global _ARMED
    _ARMED = False


def armed() -> bool:
    return _ARMED


def watch_sweeps(on: bool) -> None:
    """An observed run asks for (or stops) the sweep-boundary live-bytes
    sample of ``game/coordinate_descent.py``."""
    global _SWEEPS_WATCHED
    _SWEEPS_WATCHED = on


def sweeps_watched() -> bool:
    return _SWEEPS_WATCHED


def peak_bytes() -> int:
    """Run-wide HBM peak over every :func:`sample` so far (bytes)."""
    with _LOCK:
        return _PEAK_BYTES


def _live_bytes() -> int:
    """Σ nbytes over live arrays — metadata-only, never a device sync."""
    import jax

    try:
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in jax.live_arrays())
    except Exception:  # pragma: no cover - backend without live_arrays
        return 0


def _note_peak(n: int) -> None:
    global _PEAK_BYTES
    with _LOCK:
        if n > _PEAK_BYTES:
            _PEAK_BYTES = n


def sample(registry: Optional[MetricsRegistry] = None) -> int:
    """One heartbeat-cadence sample of every local device's memory
    stats into ``hbm_bytes{device, kind}``. Returns the total in-use
    bytes across devices (live-bytes fallback where the runtime has no
    allocator stats)."""
    if not _ARMED:
        return 0
    import jax

    reg = registry or _REGISTRY
    gauge = reg.gauge("hbm_bytes")
    total_in_use = 0
    have_stats = False
    try:
        devices = jax.local_devices()
    except RuntimeError:  # backend not initializable
        devices = []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        have_stats = True
        dev = f"{d.platform}:{d.id}"
        for kind in _STAT_KINDS:
            if kind in stats:
                gauge.set(int(stats[kind]), device=dev, kind=kind)
        total_in_use += int(stats.get("bytes_in_use", 0))
    if not have_stats:
        # CPU (and any runtime without allocator stats): the live-array
        # footprint is the best available in-use proxy
        total_in_use = _live_bytes()
        gauge.set(total_in_use, device="host", kind="live_bytes")
    _note_peak(total_in_use)
    return total_in_use


def note_coordinate(coordinate_id: str) -> None:
    """Record the current live-byte footprint against a coordinate —
    called by the CD commit path right after a block installs, so the
    per-coordinate watermark reflects that coordinate's update at its
    most buffer-heavy point the host can see."""
    if not _ARMED:
        return
    n = _live_bytes()
    _note_peak(n)
    with _LOCK:
        prev = _COORD_WATERMARKS.get(coordinate_id, 0)
        if n > prev:
            _COORD_WATERMARKS[coordinate_id] = n


def drain_coordinate_watermarks(
        sweep: int, registry: Optional[MetricsRegistry] = None) -> dict:
    """Flush the per-coordinate watermarks accumulated this sweep into
    ``hbm_watermark_bytes{coordinate}`` gauges + ``cd.hbm_watermark``
    spans (rides the sweep-boundary drain, where the hot loop already
    pays a host round-trip). Returns the drained map."""
    if not _ARMED:
        return {}
    with _LOCK:
        drained = dict(_COORD_WATERMARKS)
        _COORD_WATERMARKS.clear()
    if not drained:
        return drained
    reg = registry or _REGISTRY
    gauge = reg.gauge("hbm_watermark_bytes")
    for cid, n in sorted(drained.items()):
        gauge.set(n, coordinate=cid)
        with trace.span("cd.hbm_watermark", sweep=sweep, coordinate=cid,
                        watermark_bytes=n):
            pass
    return drained
