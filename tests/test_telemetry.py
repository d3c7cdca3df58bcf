"""Live telemetry plane: export sink, trace merge, status CLI, diffs.

Covers the streaming-observability contracts:

- endpoint parsing + NDJSON streaming to a live socket consumer,
- export durability: a dead consumer falls back to a tailable file, a
  slow/broken one only ever DROPS records (bounded queue, counted on
  ``telemetry_dropped{kind}``) and never blocks the emitting thread,
  a SIGKILLed producer leaves the consumer-side tail line-parseable,
- the ObservedRun wiring: manifest-first stream, spans/heartbeats live,
  ``run_end`` with the exit status, ``telemetry_proto`` in the manifest,
- ``tools/trace_merge.py``: one track per process, monotonic per track,
  clock-aligned on ``gang.form`` (with the start_unix fallback),
- ``tools/trace_diff.py``: PASS on identical runs, FAIL naming exactly
  the inflated span, sub-noise spans ignored,
- ``tools/photon_status.py``: status document + the 0/2/3/4 exit-code
  scripting contract,
- the tier-1 acceptance scenario: a REAL driver run streams records to
  a consumer while it is still training; killing the consumer mid-run
  changes neither the exit code nor the final objective (bit-exact);
  ``photon_status --json`` on the run dir reports sweep progress,
- a connected live sink adds no device→host fetch to a warm sweep, and
  its ``emit()`` refuses (and counts) a record where it would have to
  wait for a consumer that does not read.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.export import (
    TELEMETRY_PROTO,
    TelemetrySink,
    parse_endpoint,
)
from photon_ml_tpu.obs.metrics import MetricsRegistry
from photon_ml_tpu.obs.run import start_observed_run
from photon_ml_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(REPO, "tools")


@pytest.fixture(autouse=True)
def _isolation():
    """No leaked tracer or armed fault specs across tests."""
    yield
    trace.disable()
    faults.disarm_all()


def _tcp_server():
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    return srv, "%s:%d" % srv.getsockname()


class _Consumer:
    """Accept one connection and collect its NDJSON lines."""

    def __init__(self, srv):
        self.srv = srv
        self.raw = b""
        self.conn = None
        self.connected = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        try:
            self.conn, _ = self.srv.accept()
        except OSError:
            return
        self.connected.set()
        while True:
            try:
                chunk = self.conn.recv(65536)
            except OSError:
                return
            if not chunk:
                return
            self.raw += chunk

    def records(self):
        return [json.loads(line)
                for line in self.raw.split(b"\n") if line.strip()]

    def join(self, timeout=5.0):
        self._thread.join(timeout=timeout)


# -- endpoint parsing --------------------------------------------------------


class TestEndpointParsing:
    def test_schemes(self):
        assert parse_endpoint("127.0.0.1:9000") == \
            ("tcp", ("127.0.0.1", 9000))
        assert parse_endpoint("tcp://host:81") == ("tcp", ("host", 81))
        assert parse_endpoint("unix:/tmp/t.sock") == \
            ("unix", "/tmp/t.sock")
        assert parse_endpoint("unix:///tmp/t.sock") == \
            ("unix", "/tmp/t.sock")
        assert parse_endpoint("file:/tmp/out.jsonl") == \
            ("file", "/tmp/out.jsonl")
        # a bare path is file-tail mode
        assert parse_endpoint("/tmp/out.jsonl") == \
            ("file", "/tmp/out.jsonl")

    def test_explicit_tcp_without_port_is_an_error(self):
        """A typo'd tcp:// endpoint must fail loudly, not silently ship
        the stream into a file named after the host."""
        with pytest.raises(ValueError, match="host:port"):
            parse_endpoint("tcp://127.0.0.1")
        with pytest.raises(ValueError, match="numeric port"):
            parse_endpoint("tcp://host:https")

    def test_driver_rejects_flag_misuse_at_parse_time(self, tmp_path):
        """--telemetry-endpoint without --trace-dir (or with a broken
        tcp:// endpoint) is an argparse usage error (SystemExit 2), not
        a ValueError traceback from the obs wiring."""
        from photon_ml_tpu.cli.game_training_driver import parse_args

        base = [
            "--train-input-dirs", str(tmp_path),
            "--output-dir", str(tmp_path / "out"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map", "g:x",
            "--updating-sequence", "g",
        ]
        with pytest.raises(SystemExit) as exc:
            parse_args(base + ["--telemetry-endpoint", "127.0.0.1:9"])
        assert exc.value.code == 2
        with pytest.raises(SystemExit) as exc:
            parse_args(base + ["--trace-dir", str(tmp_path / "t"),
                               "--telemetry-endpoint", "tcp://nohost"])
        assert exc.value.code == 2
        # the valid pair parses
        ns = parse_args(base + ["--trace-dir", str(tmp_path / "t"),
                                "--telemetry-endpoint", "127.0.0.1:9"])
        assert ns.telemetry_endpoint == "127.0.0.1:9"


# -- sink durability ---------------------------------------------------------


class TestTelemetrySink:
    def test_streams_records_in_order_to_live_consumer(self):
        srv, endpoint = _tcp_server()
        consumer = _Consumer(srv)
        reg = MetricsRegistry()
        sink = TelemetrySink(endpoint, registry=reg)
        for i in range(20):
            assert sink.emit({"kind": "span", "i": i})
        sink.close()
        consumer.join()
        srv.close()
        assert [r["i"] for r in consumer.records()] == list(range(20))
        assert reg.counter("telemetry_dropped").total() == 0

    def test_dead_consumer_falls_back_to_tailable_file(self, tmp_path):
        fallback = str(tmp_path / "telemetry.jsonl")
        reg = MetricsRegistry()
        warns = []
        # a TCP port nobody serves: bind+close to get a refused port
        srv, endpoint = _tcp_server()
        srv.close()
        sink = TelemetrySink(endpoint, fallback_path=fallback,
                             registry=reg, warn=warns.append)
        for i in range(30):
            sink.emit({"kind": "heartbeat", "i": i})
        time.sleep(0.5)
        sink.close()
        with open(fallback) as fh:
            got = [json.loads(line)["i"] for line in fh]
        assert got == list(range(30))
        assert reg.counter("telemetry_dropped").total() == 0
        assert warns and "no consumer" in warns[0]

    def test_broken_export_drops_bounded_and_never_blocks(self, tmp_path):
        """The backpressure contract: telemetry I/O hard down + a tiny
        queue → records are dropped (counted by kind), emit() stays
        non-blocking, nothing raises into the emitting thread."""
        faults.arm("obs.export", "io_error", times=10 ** 9)
        reg = MetricsRegistry()
        sink = TelemetrySink(str(tmp_path / "t.jsonl"),
                             max_queued_records=8, registry=reg)
        t0 = time.perf_counter()
        for i in range(10_000):
            sink.emit({"kind": "span", "i": i})
        emit_secs = time.perf_counter() - t0
        # 10k emits against a fully-broken exporter: queue-full drops
        # only, each a counter increment — generous bound, no blocking
        assert emit_secs < 2.0, f"emit() blocked: {emit_secs:.3f}s"
        sink.close()
        dropped = reg.counter("telemetry_dropped")
        assert dropped.total() > 0
        assert dropped.value(kind="span") == dropped.total()
        assert not os.path.exists(str(tmp_path / "t.jsonl"))

    def test_consumer_killed_mid_stream_is_survivable(self, tmp_path):
        """The consumer dies after a few records: the sink must carry on
        (reconnect-blackout → fallback/drops) without raising."""
        srv, endpoint = _tcp_server()
        consumer = _Consumer(srv)
        fallback = str(tmp_path / "telemetry.jsonl")
        reg = MetricsRegistry()
        sink = TelemetrySink(endpoint, fallback_path=fallback,
                             registry=reg)
        sink.emit({"kind": "span", "i": 0})
        assert consumer.connected.wait(5.0)
        deadline = time.time() + 5
        while not consumer.raw and time.time() < deadline:
            time.sleep(0.01)
        assert consumer.raw, "consumer never heard the first record"
        # hard-kill the consumer side mid-run
        consumer.conn.setsockopt(
            socket.SOL_SOCKET, socket.SO_LINGER,
            b"\x01\x00\x00\x00\x00\x00\x00\x00")  # RST on close
        consumer.conn.close()
        srv.close()
        for i in range(1, 200):
            sink.emit({"kind": "span", "i": i})
            time.sleep(0.002)
        sink.close()
        # records are accounted for: received early, landed in the
        # fallback file after the connection died, or counted dropped.
        # (A few in-flight records can vanish in the dead socket's
        # kernel buffer — sent but never read — so the sum is an upper
        # bound, not an equality.)
        received = len(consumer.records())
        fell_back = 0
        if os.path.exists(fallback):
            with open(fallback) as fh:
                fell_back = sum(1 for line in fh if line.strip())
        dropped = reg.counter("telemetry_dropped").total()
        assert received > 0, "consumer heard nothing before dying"
        assert fell_back + dropped > 0, \
            "nothing was rerouted after the consumer died"
        assert received + fell_back + dropped <= 200, \
            (received, fell_back, dropped)

    def test_sigkilled_producer_leaves_tail_line_parseable(self, tmp_path):
        """SIGKILL the producing process mid-stream: every COMPLETE
        line on the consumer side still parses (at most the last line is
        torn) — the property tools/photon_status.py's reader and the
        chaos campaign's stream invariant both lean on."""
        srv, endpoint = _tcp_server()
        consumer = _Consumer(srv)
        script = (
            "import sys, time\n"
            "sys.path.insert(0, %r)\n"
            "from photon_ml_tpu.obs.export import TelemetrySink\n"
            "sink = TelemetrySink(%r)\n"
            "i = 0\n"
            "while True:\n"
            "    sink.emit({'kind': 'span', 'i': i, "
            "'pad': 'x' * 200})\n"
            "    i += 1\n"
            "    time.sleep(0.0005)\n" % (REPO, endpoint))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen([sys.executable, "-c", script], env=env)
        try:
            deadline = time.time() + 30
            while len(consumer.raw) < 8_000 and time.time() < deadline:
                time.sleep(0.05)
            assert len(consumer.raw) >= 8_000, "producer never streamed"
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            proc.wait(timeout=30)
            srv.close()
        raw = consumer.raw
        complete, _, _tail = raw.rpartition(b"\n")
        lines = [line for line in complete.split(b"\n") if line.strip()]
        assert len(lines) > 20
        for line in lines:
            rec = json.loads(line)  # raises on a torn/spliced line
            assert rec["kind"] == "span"


# -- ObservedRun wiring ------------------------------------------------------


class TestObservedRunTelemetry:
    def test_manifest_first_then_spans_heartbeats_run_end(self, tmp_path):
        endpoint = "file:" + str(tmp_path / "stream.jsonl")
        run = start_observed_run(str(tmp_path / "trace"),
                                 heartbeat_seconds=3600,
                                 telemetry_endpoint=endpoint)
        with trace.span("cd.update", coordinate="fixed", sweep=0):
            pass
        run.heartbeat.check()
        run.finish()
        with open(tmp_path / "stream.jsonl") as fh:
            records = [json.loads(line) for line in fh]
        kinds = [r["kind"] for r in records]
        assert kinds[0] == "run_manifest"
        assert records[0]["telemetry_proto"] == TELEMETRY_PROTO
        assert "span" in kinds and "heartbeat" in kinds
        assert kinds[-1] == "run_end"
        assert records[-1]["status"] == "ok"
        span = next(r for r in records if r["kind"] == "span")
        assert span["name"] == "cd.update"
        assert span["labels"] == {"coordinate": "fixed", "sweep": 0}
        assert span["process_index"] == 0
        hb = next(r for r in records if r["kind"] == "heartbeat")
        assert "metric_totals" in hb

    def test_exit_status_lands_in_run_end(self, tmp_path):
        endpoint = "file:" + str(tmp_path / "stream.jsonl")
        run = start_observed_run(str(tmp_path / "trace"),
                                 heartbeat_seconds=3600,
                                 telemetry_endpoint=endpoint)
        run.set_exit_status("abort", reason="ShardLossExceededError: x")
        run.finish()
        with open(tmp_path / "stream.jsonl") as fh:
            end = [json.loads(line) for line in fh][-1]
        assert end["kind"] == "run_end" and end["status"] == "abort"
        assert "ShardLossExceededError" in end["reason"]
        # the run_end record also closes the metrics stream
        with open(tmp_path / "trace" / "metrics.jsonl") as fh:
            lines = [json.loads(line) for line in fh if line.strip()]
        assert lines[-1]["kind"] == "run_end"
        assert lines[-1]["status"] == "abort"

    def test_endpoint_without_trace_dir_is_rejected(self):
        import argparse

        from photon_ml_tpu.obs.run import start_observed_run_from_flags

        ns = argparse.Namespace(trace_dir=None,
                                telemetry_endpoint="127.0.0.1:9")
        with pytest.raises(ValueError, match="requires --trace-dir"):
            start_observed_run_from_flags(ns)


# -- trace merge -------------------------------------------------------------


def _x(name, ts, dur, pid, args=None):
    return {"name": name, "cat": "photon", "ph": "X", "ts": ts,
            "dur": dur, "pid": pid, "tid": 1, "args": args or {}}


def _write_run_dir(tmp_path, with_anchor=True):
    d = str(tmp_path / "run")
    os.makedirs(d, exist_ok=True)
    # two processes whose tracer epochs are wildly different clocks
    p0 = [_x("cd.sweep", 1600, 1000, 0, {"sweep": 0}),
          _x("cd.update", 1700, 300, 0, {"sweep": 0,
                                         "coordinate": "fixed"})]
    p1 = [_x("cd.sweep", 50_500, 900, 1, {"sweep": 0})]
    if with_anchor:
        p0.insert(0, _x("gang.form", 1000, 500, 0))
        p1.insert(0, _x("gang.form", 50_000, 400, 1))
    for i, events in ((0, p0), (1, p1)):
        with open(os.path.join(d, f"trace.{i}.json"), "w") as fh:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "otherData": {"process_index": i,
                                     "start_unix_time": 100.0 + i}},
                      fh)
    return d


class TestTraceMerge:
    def _merge(self, run_dir, *extra):
        proc = subprocess.run(
            [sys.executable, os.path.join(_TOOLS, "trace_merge.py"),
             run_dir, *extra],
            capture_output=True, text=True, timeout=60)
        return proc

    def test_two_tracks_aligned_on_gang_form(self, tmp_path):
        run_dir = _write_run_dir(tmp_path)
        proc = self._merge(run_dir)
        assert proc.returncode == 0, proc.stderr
        with open(os.path.join(run_dir, "merged_trace.json")) as fh:
            doc = json.load(fh)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        # the anchor ends coincide: that IS the shared gang instant
        ends = {e["pid"]: e["ts"] + e["dur"]
                for e in xs if e["name"] == "gang.form"}
        assert ends[0] == ends[1]
        # monotonic per track, and every event non-negative
        for pid in (0, 1):
            ts = [e["ts"] for e in xs if e["pid"] == pid]
            assert ts == sorted(ts)
            assert all(t >= 0 for t in ts)
        assert doc["otherData"]["alignment"] == "gang.form"
        # per-process metadata names the tracks for the Perfetto UI
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"
                and e["name"] == "process_name"]
        assert {m["pid"] for m in meta} == {0, 1}

    def test_start_unix_fallback_without_anchor(self, tmp_path):
        run_dir = _write_run_dir(tmp_path, with_anchor=False)
        proc = self._merge(run_dir)
        assert proc.returncode == 0, proc.stderr
        with open(os.path.join(run_dir, "merged_trace.json")) as fh:
            doc = json.load(fh)
        assert doc["otherData"]["alignment"] == "start_unix"
        # process 1 started 1 s later → shifted +1e6 us
        assert doc["otherData"]["shifts_us"]["1"] == pytest.approx(1e6)

    def test_from_spans_jsonl_live_dir(self, tmp_path):
        """A run still in flight has spans.<i>.jsonl but no rebuilt
        trace.<i>.json — the merge must work from the live spill."""
        d = str(tmp_path / "live")
        os.makedirs(d)
        for i, t0 in ((0, 1000.0), (1, 90_000.0)):
            with open(os.path.join(d, f"spans.{i}.jsonl"), "w") as fh:
                for name, ts, dur in (("gang.form", t0, 400.0),
                                      ("cd.sweep", t0 + 500, 800.0)):
                    fh.write(json.dumps(
                        {"name": name, "tid": 7, "depth": 0,
                         "ts_us": ts, "dur_us": dur, "labels": {}})
                        + "\n")
                fh.write('{"torn tail')  # a live stream's last line
        proc = self._merge(d)
        assert proc.returncode == 0, proc.stderr
        with open(os.path.join(d, "merged_trace.json")) as fh:
            doc = json.load(fh)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        ends = {e["pid"]: e["ts"] + e["dur"]
                for e in xs if e["name"] == "gang.form"}
        assert ends[0] == ends[1]

    def test_empty_dir_exits_2(self, tmp_path):
        proc = self._merge(str(tmp_path))
        assert proc.returncode == 2


# -- trace diff --------------------------------------------------------------


def _profile_trace(path, fetch_dur_us):
    """A flat, realistic timeline: later spans start after earlier ones
    end, so inflating one name moves everything after it."""
    events, t = [], 0.0
    for _ in range(20):
        events.append(_x("cd.update", t, 10_000, 0))
        t += 11_000
        events.append(_x("cd.epilogue_fetch", t, fetch_dur_us, 0))
        t += fetch_dur_us + 1_000
        events.append(_x("tiny", t, 50, 0))
        t += 100
    with open(path, "w") as fh:
        json.dump({"traceEvents": events}, fh)


class TestTraceDiff:
    def _diff(self, base, new, *extra):
        return subprocess.run(
            [sys.executable, os.path.join(_TOOLS, "trace_diff.py"),
             base, new, "--json", *extra],
            capture_output=True, text=True, timeout=60)

    def test_same_config_reports_no_regression(self, tmp_path):
        base = str(tmp_path / "base.json")
        new = str(tmp_path / "new.json")
        _profile_trace(base, 8_000)
        _profile_trace(new, 8_400)  # 5% wiggle: inside the noise gate
        proc = self._diff(base, new)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["verdict"] == "PASS"
        assert report["regressions"] == []

    def test_inflated_span_is_named_exactly(self, tmp_path):
        base = str(tmp_path / "base.json")
        new = str(tmp_path / "new.json")
        _profile_trace(base, 8_000)
        _profile_trace(new, 16_000)  # +100% on ONE span
        proc = self._diff(base, new)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["verdict"] == "FAIL"
        assert report["regressions"] == ["cd.epilogue_fetch"]
        # the sub-noise span never participates either way
        tiny = next(e for e in report["spans"] if e["span"] == "tiny")
        assert tiny["status"] == "sub-noise"

    def test_unreadable_input_exits_2(self, tmp_path):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as fh:
            fh.write("{]")
        proc = self._diff(bad, bad)
        assert proc.returncode == 2


# -- photon_status -----------------------------------------------------------


def _status(run_dir, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "photon_status.py"),
         "--run-dir", run_dir, "--json", *extra],
        capture_output=True, text=True, timeout=60)


def _write_status_dir(tmp_path, stalled=False, run_end=None):
    d = str(tmp_path / "status_run")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "spans.jsonl"), "w") as fh:
        for sweep in (0, 1):
            for coord in ("fixed", "perUser"):
                fh.write(json.dumps(
                    {"name": "cd.update", "tid": 1, "depth": 1,
                     "ts_us": 1.0, "dur_us": 2.0,
                     "labels": {"coordinate": coord, "sweep": sweep}})
                    + "\n")
    with open(os.path.join(d, "metrics.jsonl"), "w") as fh:
        fh.write(json.dumps(
            {"kind": "heartbeat", "uptime_s": 5.0, "spans_closed": 4,
             "spans_dropped": 0, "last_span_close_age_s": 0.1,
             "open_spans": [], "stalled": stalled,
             "metric_totals": {"host_fetches": 8.0, "retries": 1.0,
                               "cd_inflight_updates": 2.0,
                               "telemetry_dropped": 3.0}}) + "\n")
        if run_end:
            fh.write(json.dumps({"kind": "run_end", "status": run_end,
                                 "reason": "", "uptime_s": 6.0}) + "\n")
    return d


class TestPhotonStatus:
    def test_healthy_running_run_exits_0_with_progress(self, tmp_path):
        d = _write_status_dir(tmp_path)
        proc = _status(d)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        status = json.loads(proc.stdout)
        assert status["status"] == "running"
        assert status["sweep"] == 1 and status["updates"] == 4
        p0 = status["processes"]["0"]
        assert p0["host_syncs_per_update"] == 2.0
        assert p0["inflight_pipeline_depth"] == 2.0
        assert p0["retries"] == 1.0
        assert p0["telemetry_dropped"] == 3.0
        assert p0["last_coordinate"] == "perUser"

    def test_stalled_run_exits_2(self, tmp_path):
        proc = _status(_write_status_dir(tmp_path, stalled=True))
        assert proc.returncode == 2
        assert json.loads(proc.stdout)["status"] == "stalled"

    def test_aborted_run_exits_3(self, tmp_path):
        proc = _status(_write_status_dir(tmp_path, run_end="abort"))
        assert proc.returncode == 3
        assert json.loads(proc.stdout)["status"] == "aborted"

    def test_finished_run_exits_0(self, tmp_path):
        proc = _status(_write_status_dir(tmp_path, run_end="ok"))
        assert proc.returncode == 0
        assert json.loads(proc.stdout)["status"] == "finished"

    def test_no_telemetry_exits_4(self, tmp_path):
        proc = _status(str(tmp_path))
        assert proc.returncode == 4

    def test_tailer_is_incremental(self, tmp_path):
        """--watch cost model: a second poll() reads only the bytes
        appended since the first (per-file offsets), and a torn last
        line is deferred until it completes."""
        sys.path.insert(0, _TOOLS)
        try:
            import photon_status
        finally:
            sys.path.remove(_TOOLS)
        d = _write_status_dir(tmp_path)
        tailer = photon_status.RunDirTailer(d)
        first = tailer.poll()
        assert {r["kind"] for r in first} == {"span", "heartbeat"}
        n_first = len(first)
        spans_path = os.path.join(d, "spans.jsonl")
        offset_before = tailer._offsets[spans_path]
        # append one complete span + one torn tail
        with open(spans_path, "a") as fh:
            fh.write(json.dumps(
                {"name": "cd.update", "tid": 1, "depth": 1,
                 "ts_us": 9.0, "dur_us": 1.0,
                 "labels": {"coordinate": "fixed", "sweep": 2}}) + "\n")
            fh.write('{"torn')
        second = tailer.poll()
        assert len(second) == n_first + 1
        # the offset advanced past the complete line only; the torn
        # tail stays unconsumed for the next poll
        assert tailer._offsets[spans_path] > offset_before
        with open(spans_path, "a") as fh:
            fh.write(' tail"}\n')  # the tail completes (as junk)
        third = tailer.poll()
        # no double-reads: earlier records appear exactly once, and the
        # appended cd.update advanced the computed sweep
        assert len(third) - len(second) <= 1
        assert photon_status.compute_status(third)["sweep"] == 2

    def test_human_rendering_smoke(self, tmp_path):
        d = _write_status_dir(tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join(_TOOLS, "photon_status.py"),
             "--run-dir", d],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "photon-top" in proc.stdout
        assert "perUser" in proc.stdout


# -- acceptance: the live plane on a real driver run -------------------------


def _e2e_driver_args(train, out, trace_dir):
    return [
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
        "--random-effect-data-configurations", "perUser:userId,user,1",
        "--random-effect-optimization-configurations",
        "perUser:20,1e-7,1.0,1,LBFGS,L2",
        "--trace-dir", trace_dir,
        "--trace-heartbeat-seconds", "0.2",
        "--model-output-mode", "NONE",
        "--delete-output-dir-if-exists", "true",
    ]


def _run_driver(args, timeout=300):
    env = dict(os.environ)
    env.pop("PHOTON_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.cli.game_training_driver",
         *args],
        env=env, cwd=REPO, text=True, capture_output=True,
        timeout=timeout)


def _final_objective(out_dir):
    with open(os.path.join(out_dir, "metrics.json")) as fh:
        return json.load(fh)["grid"][0]["states"][-1]["objective"]


class TestDriverLivePlane:
    def test_live_stream_consumer_kill_and_status(self, tmp_path):
        """The ISSUE acceptance scenario end to end: a real driver run
        with --telemetry-endpoint streams records a consumer reads
        WHILE the run is still training; the consumer is then killed
        mid-run; the run's exit code and final objective are identical
        to a reference run with no telemetry at all; photon_status
        --json on the run dir reports sweep progress and exits 0."""
        import test_drivers

        train = str(tmp_path / "train.avro")
        test_drivers._make_game_avro(train, n=200, seed=3)

        # -- reference: no telemetry plane at all ------------------------
        ref_out = str(tmp_path / "ref_out")
        ref = _run_driver(_e2e_driver_args(
            train, ref_out, str(tmp_path / "ref_trace")))
        assert ref.returncode == 0, ref.stderr[-2000:]
        reference_objective = _final_objective(ref_out)

        # -- live run with a consumer we kill mid-stream -----------------
        srv, endpoint = _tcp_server()
        consumer = _Consumer(srv)
        out = str(tmp_path / "out")
        trace_dir = str(tmp_path / "trace")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "photon_ml_tpu.cli.game_training_driver",
             *_e2e_driver_args(train, out, trace_dir),
             "--telemetry-endpoint", endpoint],
            env=env, cwd=REPO, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        try:
            assert consumer.connected.wait(120), \
                "driver never connected to the telemetry endpoint"
            # first record arrives BEFORE process exit — the stream is
            # live, not an exit dump
            deadline = time.time() + 120
            while b"\n" not in consumer.raw and time.time() < deadline:
                assert proc.poll() is None, \
                    "driver exited before streaming anything"
                time.sleep(0.05)
            assert proc.poll() is None, "records must stream mid-run"
            first = json.loads(consumer.raw.split(b"\n", 1)[0])
            assert first["kind"] == "run_manifest"
            assert first["telemetry_proto"] == TELEMETRY_PROTO
            # kill the consumer while the run is still going
            consumer.conn.close()
            srv.close()
            stdout, stderr = proc.communicate(timeout=300)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        assert proc.returncode == 0, stderr[-2000:]
        # a dead consumer changed NOTHING about the result
        assert _final_objective(out) == reference_objective

        # -- photon-top over the finished run dir ------------------------
        status_proc = _status(trace_dir)
        assert status_proc.returncode == 0, \
            status_proc.stdout + status_proc.stderr
        status = json.loads(status_proc.stdout)
        assert status["status"] == "finished"
        assert status["sweep"] == 1  # --num-iterations 2 → sweeps 0, 1
        assert status["updates"] >= 4
        assert status["processes"]["0"]["run_end"]["status"] == "ok"


# -- the live sink beside the hot loop: no sync, no waiting -------------------


class TestExportOverhead:
    def test_live_sink_adds_no_sync_and_never_waits_for_a_reader(self, rng):
        """A warm CD run with a CONNECTED live sink (tracing + a
        heartbeat-cadence span drain + socket export) whose consumer
        never reads: the run performs the blocking device→host fetches
        of the plain run and no other (transfer guard on), and an
        ``emit()`` that finds the queue full returns False at once — the
        record is counted on ``telemetry_dropped`` — where a sink that
        waited for room would accept every record. The share of a
        sweep's time the export costs is a chip reading (PERF.md
        section 6, PR 27)."""
        import jax

        import test_obs

        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType
        from photon_ml_tpu.utils import sync_telemetry

        coords, labels, weights, offsets = test_obs._cd_inputs(
            rng, n=240, n_entities=6)

        def fetches_of_one_run():
            sync_telemetry.reset_host_fetches()
            with jax.transfer_guard_device_to_host("disallow"):
                run_coordinate_descent(coords, 2,
                                       TaskType.LOGISTIC_REGRESSION,
                                       labels, weights, offsets)
            return sync_telemetry.host_fetch_count()

        # compile everything at these shapes OUTSIDE the guard
        run_coordinate_descent(coords, 2, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)
        plain = fetches_of_one_run()

        srv, endpoint = _tcp_server()
        held = []  # the consumer connects and never reads
        threading.Thread(target=lambda: held.append(srv.accept()),
                         daemon=True).start()
        reg = MetricsRegistry()
        sink = TelemetrySink(endpoint, max_queued_records=32, registry=reg)
        dropped = reg.counter("telemetry_dropped")
        tracer = trace.enable()
        stop = threading.Event()

        def _drain_loop():
            while not stop.wait(0.01):
                for e in tracer.drain():
                    sink.emit({"kind": "span", **e})

        drainer = threading.Thread(target=_drain_loop, daemon=True)
        drainer.start()
        n_flood, pad = 20_000, "x" * 1024  # 20 MB: no socket buffer holds it
        try:
            exported = fetches_of_one_run()
            stop.set()
            drainer.join(timeout=5)
            refused = sum(
                not sink.emit({"kind": "span", "i": i, "pad": pad})
                for i in range(n_flood))
            counted = dropped.value(kind="span")
        finally:
            trace.disable()
            stop.set()
            sink.close()
            srv.close()
            for conn, _ in held:
                conn.close()
        assert plain > 0 and exported == plain
        assert 0 < refused <= counted
