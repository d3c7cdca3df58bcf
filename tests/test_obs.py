"""Observability layer: spans, metrics, heartbeat, driver integration.

Covers the obs subsystem's contracts:

- span nesting + thread safety + Chrome-trace/JSONL export validity,
- metrics-registry label math: the labeled ``host_fetches`` counter's
  site-sum equals the legacy ``sync_telemetry.host_fetch_count()``,
- event-listener containment (a raising listener must not kill training),
- heartbeat stall detection on a deliberately hung span,
- tracing adds ZERO device→host syncs inside the CD hot loop (the
  transfer-guard proof) and a warm sweep's span count is a fixed
  function of sweeps and coordinates, not of solver iterations,
- a glmix driver run with ``--trace-dir`` produces a loadable Chrome
  trace with nested cd.sweep → cd.update → cd.epilogue_fetch spans,
  per-chunk compaction spans with active-lane counts, a metrics.jsonl
  whose per-site fetch counts sum to the legacy total, heartbeat records
  and a run manifest — and ``tools/trace_report.py`` summarizes it.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.heartbeat import Heartbeat
from photon_ml_tpu.obs.metrics import (
    REGISTRY,
    Counter,
    MetricsRegistry,
)
from photon_ml_tpu.obs.run import run_manifest, start_observed_run
from photon_ml_tpu.utils import sync_telemetry
from photon_ml_tpu.utils.events import EventEmitter, FaultEvent

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_isolation():
    """Tests must not leak an enabled process-global tracer, nor inherit
    an armed device plane (which mirrors spans into the profiler)."""
    from photon_ml_tpu.obs import compile as obs_compile

    obs_compile.disarm()
    yield
    trace.disable()


# -- span tracer -------------------------------------------------------------


class TestSpanTracer:
    def test_disabled_tracing_is_a_shared_noop(self):
        trace.disable()
        s1 = trace.span("a", x=1)
        s2 = trace.span("b")
        assert s1 is s2  # the singleton: no allocation when disabled
        with s1:
            pass

    def test_nesting_depth_and_labels(self):
        t = trace.enable()
        with trace.span("outer", sweep=0):
            with trace.span("inner", coordinate="fixed"):
                pass
            with trace.span("inner", coordinate="perUser"):
                pass
        events = t.events()
        assert [e["name"] for e in events] == ["inner", "inner", "outer"]
        by_depth = {(e["name"], e["depth"]) for e in events}
        assert ("outer", 0) in by_depth and ("inner", 1) in by_depth
        outer = events[-1]
        assert outer["labels"] == {"sweep": 0}
        # children contained in the parent's [ts, ts+dur] interval
        for child in events[:2]:
            assert child["ts_us"] >= outer["ts_us"]
            assert (child["ts_us"] + child["dur_us"]
                    <= outer["ts_us"] + outer["dur_us"] + 1e-3)

    def test_late_labels_reach_the_record(self):
        """``label()`` adds what only the finished work knows (a compile's
        seconds); on the no-op span it is a no-op too."""
        trace.span("off").label(secs=1.0)  # tracing off: nothing to keep
        t = trace.enable()
        with trace.span("xla.compile", site="s") as span:
            span.label(secs=0.5, flops=8.0)
        (event,) = t.events()
        assert event["labels"] == {"site": "s", "secs": 0.5, "flops": 8.0}

    def test_mirrored_spans_record_as_before(self):
        """With the mirror into the profiler on (no capture running), a
        span records into the tracer exactly as without it, and without a
        tracer it is still a context manager that nests."""
        try:
            trace.mirror_to_profiler(True)
            with trace.span("outer", sweep=1) as outer:
                with trace.span("inner"):
                    outer.label(late=True)
            t = trace.enable()
            with trace.span("outer", sweep=1):
                with trace.span("inner"):
                    pass
        finally:
            trace.mirror_to_profiler(False)
        assert [(e["name"], e["depth"], e["labels"]) for e in t.events()] \
            == [("inner", 1, {}), ("outer", 0, {"sweep": 1})]
        assert trace.span("outer") is not trace._NULL_SPAN
        trace.disable()
        assert trace.span("outer") is trace._NULL_SPAN

    def test_armed_keeps_spans_in_the_one_store(self):
        """Arming installs a tracer of the newest ARMED_MAX_BUFFERED_SPANS
        where there is none; a tracer that was enabled (an observed run's)
        stays the store, and disarming takes away only its own."""
        from photon_ml_tpu.obs import compile as obs_compile

        assert trace.get_tracer() is None
        obs_compile.arm()
        armed = trace.get_tracer()
        assert armed.max_buffered_spans == trace.ARMED_MAX_BUFFERED_SPANS
        with trace.span("kept", n=1):
            pass
        assert [(e["name"], e["labels"]) for e in armed.events()] \
            == [("kept", {"n": 1})]
        obs_compile.arm()  # idempotent: the same store
        assert trace.get_tracer() is armed
        mine = trace.enable()  # an observed run installs its own over it
        with trace.span("observed"):
            pass
        assert [e["name"] for e in mine.events()] == ["observed"]
        assert len(armed.events()) == 1
        obs_compile.disarm()
        assert trace.get_tracer() is mine  # not arming's to take away
        trace.disable()
        obs_compile.arm()
        assert trace.get_tracer() not in (None, armed, mine)
        obs_compile.disarm()
        assert trace.get_tracer() is None
        assert trace.span("off") is trace._NULL_SPAN

    def test_thread_safety(self):
        t = trace.enable()
        n_threads, n_spans = 8, 200
        errors = []

        def work(i):
            try:
                for j in range(n_spans):
                    with trace.span("w", thread=i, j=j):
                        with trace.span("w.inner"):
                            pass
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        events = t.events()
        assert len(events) == n_threads * n_spans * 2
        # per-thread nesting stayed consistent: every inner span is depth
        # 1, every outer depth 0, regardless of interleaving
        assert {e["depth"] for e in events if e["name"] == "w"} == {0}
        assert {e["depth"] for e in events if e["name"] == "w.inner"} == {1}

    def test_chrome_trace_and_jsonl_validity(self, tmp_path):
        t = trace.enable()
        with trace.span("parent", kind="test"):
            with trace.span("child"):
                time.sleep(0.001)
        chrome_path = str(tmp_path / "trace.json")
        jsonl_path = str(tmp_path / "spans.jsonl")
        t.write_chrome_trace(chrome_path)
        t.write_spans_jsonl(jsonl_path)

        with open(chrome_path) as fh:
            doc = json.loads(fh.read())
        events = doc["traceEvents"]
        assert events, "no trace events written"
        for e in events:
            assert e["ph"] == "X"
            assert "ts" in e and "name" in e and "dur" in e
            assert "pid" in e and "tid" in e
        assert {e["name"] for e in events} == {"parent", "child"}

        with open(jsonl_path) as fh:
            lines = [json.loads(line) for line in fh if line.strip()]
        assert len(lines) == 2
        for rec in lines:
            assert {"name", "ts_us", "dur_us", "depth", "labels"} <= set(rec)


# -- metrics registry --------------------------------------------------------


class TestMetricsRegistry:
    def test_site_label_sum_equals_legacy_host_fetch_count(self):
        sync_telemetry.reset_host_fetches()
        sync_telemetry.record_host_fetch()                       # unlabeled
        sync_telemetry.record_host_fetch(site="cd.epilogue")
        sync_telemetry.record_host_fetch(2, site="cd.epilogue")
        sync_telemetry.record_host_fetch(site="tracker.materialize")
        by_site = sync_telemetry.host_fetches_by_site()
        assert by_site == {"unlabeled": 1, "cd.epilogue": 3,
                           "tracker.materialize": 1}
        assert sum(by_site.values()) == sync_telemetry.host_fetch_count()
        assert sync_telemetry.host_fetch_count() == 5
        # and the registry's counter view agrees with the shim's
        c = REGISTRY.counter(sync_telemetry.HOST_FETCH_COUNTER)
        assert c.total() == 5
        assert c.value(site="cd.epilogue") == 3

    def test_counter_gauge_histogram_snapshot(self):
        r = MetricsRegistry()
        r.counter("faults").inc(point="cd.update")
        r.counter("faults").inc(2, point="ckpt.save")
        r.gauge("active_lanes").set(7, coordinate="perUser")
        h = r.histogram("iters", buckets=[1, 4, 16])
        for x in (0, 3, 3, 20):
            h.observe(x)
        records = r.snapshot()
        kinds = {(rec["kind"], rec["name"]) for rec in records}
        assert ("counter", "faults") in kinds
        assert ("gauge", "active_lanes") in kinds
        assert ("histogram", "iters") in kinds
        hist = next(rec for rec in records if rec["kind"] == "histogram")
        assert hist["count"] == 4 and hist["min"] == 0 and hist["max"] == 20
        # cumulative Prometheus semantics: le_X = observations <= X
        assert hist["buckets"] == {"le_1": 1, "le_4": 3, "le_16": 3,
                                   "le_inf": 4}

    def test_metric_kind_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")
        # and the reverse order too (Gauge subclasses Counter — the check
        # must be exact-type, not isinstance)
        r.gauge("y")
        with pytest.raises(TypeError):
            r.counter("y")

    def test_reset_zeroes_but_keeps_registration(self):
        r = MetricsRegistry()
        r.counter("n").inc(5, site="a")
        r.reset()
        assert r.counter("n").total() == 0
        assert isinstance(r.counter("n"), Counter)


# -- event-listener containment (satellite bugfix) ---------------------------


class TestListenerContainment:
    def test_raising_listener_is_contained_and_counted(self):
        before = REGISTRY.counter("listener_errors").total()
        emitter = EventEmitter()
        seen = []

        def bad(event):
            raise ValueError("broken log shipper")

        emitter.register_listener(bad)
        emitter.register_listener(seen.append)
        # must NOT propagate into the (simulated) training loop ...
        emitter.send_event(FaultEvent(point="cd.update"))
        # ... later listeners still ran, and the failure was counted
        assert len(seen) == 1
        assert REGISTRY.counter("listener_errors").total() == before + 1


# -- heartbeat / stall detection ---------------------------------------------


class TestHeartbeat:
    def test_stall_fires_on_hung_span(self, tmp_path):
        t = trace.enable()
        out = str(tmp_path / "metrics.jsonl")
        hb = Heartbeat(t, out_path=out, interval_seconds=60,
                       stall_seconds=0.05)
        stalls_before = REGISTRY.counter("stalls").total()
        # a deliberately hung span: entered, never exits
        hung = t.span("cd.update", coordinate="perUser").__enter__()
        time.sleep(0.1)
        record = hb.check()
        assert record["stalled"] is True
        assert "cd.update" in record["open_spans"]
        assert record["last_span_close_age_s"] > 0.05
        assert REGISTRY.counter("stalls").total() == stalls_before + 1
        # the record landed in the metrics stream
        with open(out) as fh:
            lines = [json.loads(line) for line in fh]
        assert lines and lines[-1]["kind"] == "heartbeat"
        assert lines[-1]["stalled"] is True
        # closing the span clears the stall on the next beat
        hung.__exit__(None, None, None)
        record = hb.check()
        assert record["stalled"] is False
        # a recovered→stalled transition counts again, but staying
        # stalled must not re-count (one increment per episode)
        assert REGISTRY.counter("stalls").total() == stalls_before + 1

    def test_heartbeat_thread_emits_records(self, tmp_path):
        t = trace.enable()
        out = str(tmp_path / "metrics.jsonl")
        hb = Heartbeat(t, out_path=out, interval_seconds=0.02,
                       stall_seconds=60).start()
        time.sleep(0.15)
        hb.stop()
        with open(out) as fh:
            lines = [json.loads(line) for line in fh]
        assert len(lines) >= 2
        assert all(rec["kind"] == "heartbeat" for rec in lines)
        assert all(rec["stalled"] is False for rec in lines)


# -- hot-loop contracts: zero syncs, span count fixed by updates ------------


def _cd_inputs(rng, **kwargs):
    import test_sync_discipline as tsd

    data, *_ = tsd.make_game_data(rng, **kwargs)
    coords = tsd._build_coords(data)
    return (coords, jnp.asarray(data.responses),
            jnp.asarray(data.weights), jnp.asarray(data.offsets))


def _two_budgets(rng):
    """The same tiny GAME data under solver budgets of 20 and of 3
    iterations: ``(long_solves, short_solves, labels, weights, offsets)``."""
    import test_sync_discipline as tsd

    data, *_ = tsd.make_game_data(rng, n=240, n_entities=6)
    return (tsd._build_coords(data, max_iter=20),
            tsd._build_coords(data, max_iter=3),
            jnp.asarray(data.responses), jnp.asarray(data.weights),
            jnp.asarray(data.offsets))


def _span_counts(coords, sweeps, labels, weights, offsets):
    """Spans by name of one traced CD run."""
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.optimize.config import TaskType

    tracer = trace.enable()
    run_coordinate_descent(coords, sweeps, TaskType.LOGISTIC_REGRESSION,
                           labels, weights, offsets)
    trace.disable()
    return collections.Counter(e["name"] for e in tracer.events())


class TestHotLoopContracts:
    def test_tracing_adds_zero_device_syncs(self, rng):
        """The transfer-guard proof: a TRACED CD sweep still performs
        exactly one blocking device→host fetch per coordinate update —
        spans are host-side only, so enabling tracing cannot add a sync."""
        from photon_ml_tpu.game import coordinate_descent as cd
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType

        coords, labels, weights, offsets = _cd_inputs(
            rng, n=240, n_entities=6)
        # compile everything at these shapes OUTSIDE the guard
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)

        tracer = trace.enable()
        cd.reset_hot_loop_stats()
        sync_telemetry.reset_host_fetches()
        with jax.transfer_guard_device_to_host("disallow"):
            res = run_coordinate_descent(
                coords, 1, TaskType.LOGISTIC_REGRESSION,
                labels, weights, offsets)
        assert len(res.states) == len(coords)
        assert cd.HOT_LOOP_STATS["updates"] == len(coords)
        assert (cd.HOT_LOOP_STATS["epilogue_fetches"]
                == cd.HOT_LOOP_STATS["updates"])
        # same contract as the untraced sweep: 1 epilogue fetch/update +
        # the sweep-boundary tracker drain
        assert sync_telemetry.host_fetch_count() == 2 * len(coords)
        # and the trace actually recorded the hot path, nested
        names = [e["name"] for e in tracer.events()]
        assert "cd.sweep" in names and "cd.update" in names
        assert "cd.epilogue_fetch" in names
        by_name = {}
        for e in tracer.events():
            by_name.setdefault(e["name"], []).append(e)
        sweep = by_name["cd.sweep"][0]
        for upd in by_name["cd.update"]:
            assert upd["ts_us"] >= sweep["ts_us"]
            assert (upd["ts_us"] + upd["dur_us"]
                    <= sweep["ts_us"] + sweep["dur_us"] + 1e-3)

    def test_traced_spans_count_updates_not_solver_iterations(self, rng):
        """What tracing costs the host is spans, and a warm sweep's spans
        are a fixed function of sweeps and coordinates: the same on the
        second warm run as on the first, twice as many for two sweeps,
        and the same whether the solvers run 3 iterations or 20 (no span
        is opened inside a solver's loop). The share of a sweep's time
        that is was read on the chip (PERF.md section 6, PR 27); the
        sibling test above holds the zero-sync half."""
        long_solves, short_solves, *arrays = _two_budgets(rng)

        def span_counts(coords, sweeps):
            return _span_counts(coords, sweeps, *arrays)

        first = span_counts(long_solves, 1)
        n_coords = len(long_solves)
        assert first["cd.sweep"] == 1
        assert first["cd.update"] == n_coords
        assert first["cd.dispatch"] == n_coords
        assert first["cd.epilogue_fetch"] == n_coords
        # one solve span a coordinate, however many iterations it ran
        assert first["optimizer.solve"] + first["re.solve"] == n_coords
        assert span_counts(long_solves, 1) == first
        assert span_counts(short_solves, 1) == first
        assert span_counts(long_solves, 2) == collections.Counter(
            {name: 2 * n for name, n in first.items()})


# -- run manifest ------------------------------------------------------------


class TestRunManifest:
    def test_manifest_contents(self):
        m = run_manifest(flags={"num_iterations": 2, "trace_dir": "/x",
                                "_obj": object()}, process_index=0)
        assert m["jax_version"] == jax.__version__
        assert m["backend"] == jax.default_backend()
        assert m["device_count"] == jax.device_count()
        # non-scalar flag values are dropped, scalars kept
        assert m["flags"]["num_iterations"] == 2
        assert "_obj" not in m["flags"]

    def test_multiprocess_file_suffixes(self, tmp_path):
        run = start_observed_run(str(tmp_path), process_index=1,
                                 num_processes=2, heartbeat_seconds=60)
        # multi-host: the first manifest write must NOT probe the backend
        # (probing initializes it, which would break the worker's later
        # jax.distributed.initialize) — fields are deferred ...
        with open(tmp_path / "run_manifest.1.json") as fh:
            assert json.load(fh)["backend"] == "deferred"
        with trace.span("x"):
            pass
        run.finish()
        assert os.path.exists(tmp_path / "trace.1.json")
        assert os.path.exists(tmp_path / "metrics.1.jsonl")
        assert os.path.exists(tmp_path / "spans.1.jsonl")
        # ... and filled in at finish(), when the gang is formed
        with open(tmp_path / "run_manifest.1.json") as fh:
            m = json.load(fh)
        assert m["backend"] == jax.default_backend()
        assert m["device_count"] >= 1


# -- span spill, buffer bound, relaunch preservation -------------------------


class TestObservedRunDurability:
    def test_buffer_cap_counts_drops_without_breaking_stall_signal(self):
        t = trace.Tracer(max_buffered_spans=3)
        for i in range(5):
            with t.span("s", i=i):
                pass
        assert len(t.events()) == 3
        assert t.spans_dropped == 2
        # the stall signal counts every close, dropped record or not
        assert t.spans_closed == 5

    def test_a_full_buffer_keeps_the_newest_and_lets_the_oldest_go(self):
        """The store is a ring: a tracer nobody drains holds its newest
        ``max_buffered_spans`` (what a reader wants after a window), and
        a drain gives the ring its whole room back."""
        t = trace.Tracer(max_buffered_spans=3)
        for i in range(5):
            with t.span("s", i=i):
                pass
        assert [e["labels"]["i"] for e in t.events()] == [2, 3, 4]
        assert [e["labels"]["i"] for e in t.drain()] == [2, 3, 4]
        assert t.events() == [] and t.spans_dropped == 2
        for i in range(5, 9):
            with t.span("s", i=i):
                pass
        assert [e["labels"]["i"] for e in t.events()] == [6, 7, 8]
        assert (t.spans_dropped, t.spans_closed) == (3, 9)

    def test_drain_empties_buffer_and_keeps_recording(self):
        t = trace.Tracer()
        with t.span("a"):
            pass
        drained = t.drain()
        assert [e["name"] for e in drained] == ["a"]
        assert t.events() == []
        with t.span("b"):
            pass
        assert [e["name"] for e in t.events()] == ["b"]

    def test_heartbeat_spills_spans_before_finish(self, tmp_path):
        """A killed run keeps every span spilled so far: spans.jsonl is
        written on the heartbeat, not only at finish()."""
        run = start_observed_run(str(tmp_path), heartbeat_seconds=3600)
        with trace.span("pre_crash", sweep=0):
            pass
        run.heartbeat.check()  # one beat, no sleeping
        with open(tmp_path / "spans.jsonl") as fh:
            spilled = [json.loads(line) for line in fh]
        assert [e["name"] for e in spilled] == ["pre_crash"]
        # ... and the tracer's buffer is drained, not duplicated
        assert run.tracer.events() == []
        with trace.span("post_beat"):
            pass
        run.finish()
        with open(tmp_path / "trace.json") as fh:
            names = [e["name"] for e in json.load(fh)["traceEvents"]]
        assert sorted(names) == ["post_beat", "pre_crash"]

    def test_spill_retains_spans_when_write_fails(self, tmp_path):
        """A transient write failure (full disk, vanished dir) must not
        lose drained spans: they stay pending and spill on the next
        beat."""
        run = start_observed_run(str(tmp_path), heartbeat_seconds=3600)
        real_path = run.spans_path
        run.spans_path = str(tmp_path / "missing_dir" / "spans.jsonl")
        with trace.span("during_outage"):
            pass
        run.heartbeat.check()  # spill fails, contained by the beat guard
        run.spans_path = real_path
        with trace.span("after_recovery"):
            pass
        run.finish()
        with open(real_path) as fh:
            names = [json.loads(line)["name"] for line in fh]
        assert names == ["during_outage", "after_recovery"]

    def test_heartbeat_restart_after_stop_beats_again(self):
        t = trace.Tracer()
        hb = Heartbeat(t, interval_seconds=0.02)
        hb.start()
        hb.stop()
        beats_before = hb.beats
        hb.start()  # the restart contract: the loop must actually run
        deadline = time.time() + 5
        while hb.beats <= beats_before and time.time() < deadline:
            time.sleep(0.01)
        hb.stop()
        assert hb.beats > beats_before

    def test_heartbeat_nonpositive_interval_disables_daemon(self):
        t = trace.Tracer()
        hb = Heartbeat(t, interval_seconds=0)
        assert hb.start()._thread is None  # no busy-loop daemon
        hb.check()  # manual evaluation still works
        assert hb.beats == 1

    def test_histogram_bucket_conflict_raises(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=[1, 2])
        reg.histogram("h")  # no explicit buckets: the existing one wins
        with pytest.raises(ValueError, match="already registered"):
            reg.histogram("h", buckets=[1, 5])

    def test_preserve_existing_keeps_crashed_incarnation_evidence(
            self, tmp_path):
        run1 = start_observed_run(str(tmp_path), heartbeat_seconds=3600)
        with trace.span("incarnation_one"):
            pass
        run1.heartbeat.check()
        run1.finish()
        with open(tmp_path / "metrics.jsonl") as fh:
            lines_before = fh.read().splitlines()
        assert lines_before

        # a supervisor relaunch must append, not truncate
        run2 = start_observed_run(str(tmp_path), heartbeat_seconds=3600,
                                  preserve_existing=True)
        with trace.span("incarnation_two"):
            pass
        run2.finish()
        with open(tmp_path / "metrics.jsonl") as fh:
            lines_after = fh.read().splitlines()
        # run1's full stream survives as a prefix, then the restart marker
        assert lines_after[:len(lines_before)] == lines_before
        assert json.loads(
            lines_after[len(lines_before)])["kind"] == "run_restart"
        # run1's trace/spans/manifest were rotated aside, not destroyed
        with open(tmp_path / "spans.jsonl.prev") as fh:
            prev = [json.loads(line) for line in fh]
        assert [e["name"] for e in prev] == ["incarnation_one"]
        assert os.path.exists(tmp_path / "trace.json.prev")
        assert os.path.exists(tmp_path / "run_manifest.json.prev")
        with open(tmp_path / "trace.json") as fh:
            names = [e["name"] for e in json.load(fh)["traceEvents"]]
        assert names == ["incarnation_two"]


# -- driver integration + trace_report (acceptance) --------------------------


class TestDriverTraceDir:
    @pytest.fixture(scope="class")
    def traced_run(self, tmp_path_factory):
        """One glmix driver run with --trace-dir + lane compaction."""
        import test_drivers

        tmp_path = tmp_path_factory.mktemp("traced")
        train = str(tmp_path / "train.avro")
        test_drivers._make_game_avro(train, n=250, seed=3)
        trace_dir = str(tmp_path / "trace")
        out = str(tmp_path / "out")
        sync_telemetry.reset_host_fetches()
        from photon_ml_tpu.cli.game_training_driver import main as game_main

        game_main([
            "--train-input-dirs", train,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:20,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:30,1e-7,1.0,1,LBFGS,L2",
            "--re-lane-compaction-chunk", "4",
            "--trace-dir", trace_dir,
            "--trace-heartbeat-seconds", "0.2",
        ])
        return trace_dir

    def test_chrome_trace_loads_with_nested_cd_spans(self, traced_run):
        with open(os.path.join(traced_run, "trace.json")) as fh:
            doc = json.loads(fh.read())
        events = doc["traceEvents"]
        assert events
        for e in events:
            assert e["ph"] == "X" and "ts" in e and "name" in e
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)

        def contained(inner, outers):
            return any(
                o["ts"] <= inner["ts"]
                and inner["ts"] + inner["dur"] <= o["ts"] + o["dur"] + 1e-3
                for o in outers)

        # nested cd.sweep → cd.update → cd.epilogue_fetch
        assert len(by_name.get("cd.sweep", [])) == 2  # --num-iterations 2
        updates = by_name["cd.update"]
        assert {u["args"]["coordinate"] for u in updates} \
            == {"fixed", "perUser"}
        for u in updates:
            assert contained(u, by_name["cd.sweep"])
        for f in by_name["cd.epilogue_fetch"]:
            assert contained(f, updates)
        # per-chunk compaction spans carry active-lane counts (the
        # ROADMAP auto-tuner's iteration histogram)
        chunks = by_name.get("re.compact_chunk", [])
        assert chunks, "lane-compaction chunks produced no spans"
        lanes = [c["args"]["active_lanes"] for c in chunks]
        assert all(isinstance(x, int) and x >= 1 for x in lanes)
        # optimizer + checkpoint-free run still shows solver spans
        assert "optimizer.solve" in by_name
        assert "re.solve" in by_name

    def test_metrics_jsonl_site_sum_and_heartbeats(self, traced_run):
        with open(os.path.join(traced_run, "metrics.jsonl")) as fh:
            lines = [json.loads(line) for line in fh if line.strip()]
        fetch_lines = [rec for rec in lines
                       if rec.get("kind") == "counter"
                       and rec.get("name") == "host_fetches"]
        assert fetch_lines, "no host_fetches counters in metrics.jsonl"
        per_site = {rec["labels"]["site"]: rec["value"]
                    for rec in fetch_lines}
        # per-site counts sum to the legacy process-wide total
        assert sum(per_site.values()) == sync_telemetry.host_fetch_count()
        assert "cd.epilogue" in per_site
        # retrace counters landed too (epilogue-cache misses et al)
        assert any(rec.get("name") == "retraces" for rec in lines)
        # live heartbeat records, none stalled
        beats = [rec for rec in lines if rec.get("kind") == "heartbeat"]
        assert beats
        assert all(rec["stalled"] is False for rec in beats)

    def test_run_manifest_written(self, traced_run):
        with open(os.path.join(traced_run, "run_manifest.json")) as fh:
            m = json.load(fh)
        assert m["jax_version"] == jax.__version__
        assert m["device_count"] >= 1
        assert m["flags"]["num_iterations"] == 2
        assert m["flags"]["re_lane_compaction_chunk"] == 4

    def test_trace_report_smoke(self, traced_run):
        """tools/trace_report.py on an in-test trace: exit 0 and a
        non-empty table with the hot-path spans + sweep attribution."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             os.path.join(traced_run, "trace.json"), "--top", "10"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "cd.update" in proc.stdout
        assert "per-coordinate sweep attribution" in proc.stdout
        assert "perUser" in proc.stdout

    def test_trace_report_rejects_garbage(self, tmp_path):
        bad = tmp_path / "not_a_trace.json"
        bad.write_text("{]")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             str(bad)], capture_output=True, text=True, timeout=60)
        assert proc.returncode == 2
