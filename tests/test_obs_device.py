"""Device-plane observability: compile/retrace attribution + HBM gauges.

The ``--device-telemetry`` contracts:

- ``obs.compile.call`` is a passthrough while disarmed; armed, it
  compiles each (site, abstract signature) exactly once, attributes the
  compile (``compiles{site}`` / ``compile_secs{site}`` counters,
  ``xla.compile`` span with cost-analysis flops/bytes), answers repeat
  signatures from its executable cache with identical results, and
  names the changed argument (shape / dtype / static value) in an
  ``xla.retrace`` record when a warm site recompiles;
- a call under active jax tracing (vmap/jit/shard_map) bypasses the
  layer entirely;
- the ARMED warm CD sweep performs zero retraces, zero added
  device→host syncs (transfer-guard proof), and emits records that
  are a fixed function of sweeps and coordinates, not of solver
  iterations (the span-tracing contract extended to the device plane);
- ``obs.devicemem`` samples HBM gauges (live-bytes fallback on CPU),
  tracks the run peak, and drains per-coordinate watermarks;
- an ``ObservedRun(device_telemetry=True)`` stamps ``peak_hbm_bytes``
  on its ``run_end`` record, and the flag without ``--trace-dir`` is a
  usage error.
"""

import collections
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.obs import compile as obs_compile
from photon_ml_tpu.obs import devicemem, trace
from photon_ml_tpu.obs.metrics import MetricsRegistry
from photon_ml_tpu.obs.run import (
    start_observed_run,
    start_observed_run_from_flags,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _device_plane_isolation():
    """Arm/disarm state and site caches must not leak across tests."""
    yield
    obs_compile.disarm()
    obs_compile.reset()
    devicemem.disarm()
    trace.disable()


@pytest.fixture()
def registry():
    return MetricsRegistry()


def _cd_inputs(rng, **kwargs):
    import test_sync_discipline as tsd

    data, *_ = tsd.make_game_data(rng, **kwargs)
    coords = tsd._build_coords(data)
    return (coords, jnp.asarray(data.responses),
            jnp.asarray(data.weights), jnp.asarray(data.offsets))


# -- the compile/retrace attribution layer -----------------------------------


class TestCompileLayer:
    def test_disarmed_is_a_passthrough(self):
        f = jax.jit(lambda x: x * 2.0)
        x = jnp.arange(4, dtype=jnp.float32)
        out = obs_compile.call("t.disarmed", f, (x,))
        np.testing.assert_allclose(np.asarray(out), np.asarray(f(x)))
        # no site state is even created
        assert "t.disarmed" not in obs_compile._SITES

    def test_compiles_once_with_cost_attribution(self, registry):
        obs_compile.arm(registry=registry)
        tracer = trace.enable()
        f = jax.jit(lambda x, y: (x @ y).sum())
        x = jnp.ones((8, 4), jnp.float32)
        y = jnp.ones((4, 3), jnp.float32)
        r1 = obs_compile.call("t.once", f, (x, y), arg_names=("x", "y"))
        r2 = obs_compile.call("t.once", f, (x, y), arg_names=("x", "y"))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(f(x, y)))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))
        # exactly one compile, timed and span-recorded
        assert registry.counter("compiles").total() == 1
        assert registry.counter("compile_secs").total() > 0
        spans = [e for e in tracer.events() if e["name"] == "xla.compile"]
        assert len(spans) == 1
        labels = spans[0]["labels"]
        assert labels["site"] == "t.once"
        assert labels["secs"] > 0
        # the CPU backend reports a cost analysis: flops ride the span
        # and the gauge trace_report --device joins on
        assert labels.get("flops", 0) > 0
        assert [r["value"] for r in registry.gauge("xla_flops").records()
                if r["labels"].get("site") == "t.once"]

    def test_retrace_cause_names_the_changed_argument(self, registry):
        obs_compile.arm(registry=registry)
        tracer = trace.enable()
        f = jax.jit(lambda x, y: (x @ y).sum())
        y = jnp.ones((4, 3), jnp.float32)
        obs_compile.call("t.shape", f, (jnp.ones((8, 4), jnp.float32), y),
                         arg_names=("X", "y"))
        # shape-perturbed second call: the acceptance scenario — the
        # retrace record must name X and its old/new shapes
        obs_compile.call("t.shape", f, (jnp.ones((9, 4), jnp.float32), y),
                         arg_names=("X", "y"))
        assert registry.counter("compiles").total() == 2
        retraces = [e for e in tracer.events()
                    if e["name"] == "xla.retrace"]
        assert len(retraces) == 1
        cause = retraces[0]["labels"]
        assert cause["site"] == "t.shape"
        assert cause["arg"] == "X"
        assert cause["field"] == "shape"
        assert "[8, 4]" in cause["old"] and "[9, 4]" in cause["new"]
        causes = registry.counter("retrace_causes").records()
        assert [r for r in causes if r["labels"] == {
            "site": "t.shape", "field": "shape"}]

    def test_retrace_cause_static_value_and_dtype(self, registry):
        obs_compile.arm(registry=registry)
        tracer = trace.enable()
        f = jax.jit(lambda x, n: x * n, static_argnums=(1,))
        x32 = jnp.ones(4, jnp.float32)
        obs_compile.call("t.static", f, (x32, 2), static_argnums=(1,),
                         arg_names=("x", "n"))
        obs_compile.call("t.static", f, (x32, 3), static_argnums=(1,),
                         arg_names=("x", "n"))
        obs_compile.call("t.static", f, (jnp.ones(4, jnp.float64), 3),
                         static_argnums=(1,), arg_names=("x", "n"))
        fields = {e["labels"]["arg"]: e["labels"]["field"]
                  for e in tracer.events() if e["name"] == "xla.retrace"}
        assert fields == {"n": "static_value", "x": "dtype"}

    def test_statics_stripped_on_cache_hit(self, registry):
        obs_compile.arm(registry=registry)
        f = jax.jit(lambda x, n: x * n, static_argnums=(1,))
        x = jnp.arange(5, dtype=jnp.float32)
        r1 = obs_compile.call("t.strip", f, (x, 3), static_argnums=(1,))
        r2 = obs_compile.call("t.strip", f, (x, 3), static_argnums=(1,))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(x) * 3)
        np.testing.assert_allclose(np.asarray(r2), np.asarray(x) * 3)
        assert registry.counter("compiles").total() == 1

    def test_bypassed_under_active_tracing(self, registry):
        """A call() that happens while jax is tracing (the vmapped
        per-entity solver path) must not try to AOT-compile — it folds
        into the outer executable."""
        obs_compile.arm(registry=registry)
        inner = jax.jit(lambda x: x + 1.0)

        @jax.jit
        def outer(x):
            return obs_compile.call("t.inner", inner, (x,))

        out = outer(jnp.ones(3, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        assert "t.inner" not in obs_compile._SITES
        assert registry.counter("compiles").total() == 0

    def test_non_lowerable_fn_falls_back_to_plain_call(self, registry):
        obs_compile.arm(registry=registry)

        def plain(x):  # not jit-wrapped: no .lower — permanent fallback
            return x * 2.0

        x = jnp.ones(3, jnp.float32)
        r1 = obs_compile.call("t.fallback", plain, (x,))
        r2 = obs_compile.call("t.fallback", plain, (x,))
        np.testing.assert_allclose(np.asarray(r1), 2.0)
        np.testing.assert_allclose(np.asarray(r2), 2.0)
        # the failed AOT attempt is still attributed as the compile cost
        assert registry.counter("compiles").total() == 1


# -- armed hot-loop contracts ------------------------------------------------


class TestArmedHotLoopContracts:
    def test_warm_cd_sweep_zero_retraces(self, rng, registry):
        """A second (warm) armed CD run compiles NOTHING new."""
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType

        coords, labels, weights, offsets = _cd_inputs(
            rng, n=240, n_entities=6)
        obs_compile.arm(registry=registry)
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)
        cold_compiles = registry.counter("compiles").total()
        assert cold_compiles > 0, \
            "armed cold pass attributed no compiles: the layer is not " \
            "wired into the CD path"
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)
        warm_delta = registry.counter("compiles").total() - cold_compiles
        assert warm_delta == 0, \
            f"warm armed CD pass recompiled {warm_delta} site(s)"

    def test_armed_adds_zero_device_syncs(self, rng, registry):
        """Transfer-guard proof for the DEVICE plane: signature building
        and live-bytes accounting are metadata-only, so an armed warm
        sweep performs the same single blocking fetch per update."""
        from photon_ml_tpu.game import coordinate_descent as cd
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType
        from photon_ml_tpu.utils import sync_telemetry

        coords, labels, weights, offsets = _cd_inputs(
            rng, n=240, n_entities=6)
        obs_compile.arm(registry=registry)
        devicemem.arm(registry=registry)
        # compile everything at these shapes OUTSIDE the guard
        run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                               labels, weights, offsets)
        cd.reset_hot_loop_stats()
        sync_telemetry.reset_host_fetches()
        with jax.transfer_guard_device_to_host("disallow"):
            res = run_coordinate_descent(
                coords, 1, TaskType.LOGISTIC_REGRESSION,
                labels, weights, offsets)
        assert len(res.states) == len(coords)
        assert sync_telemetry.host_fetch_count() == 2 * len(coords)
        # and the armed run attributed watermarks without syncing
        assert devicemem.peak_bytes() > 0

    def test_armed_alone_records_spans_and_runs_no_live_array_walk(
            self, rng, monkeypatch):
        """Armed without an observed run (what every benchmark run is): a
        sweep's spans are kept in the store arming installed, the sweep
        still survives the device-to-host transfer guard with one fetch an
        update beside its tracker's drain, and ``_sample_live_bytes`` does
        not walk
        ``jax.live_arrays()`` merely because spans are recorded. An
        observed run (``devicemem.watch_sweeps``, which ``obs/run.py``
        switches) gets its two walks a sweep."""
        from photon_ml_tpu.game import coordinate_descent as cd
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType
        from photon_ml_tpu.utils import sync_telemetry

        coords, labels, weights, offsets = _cd_inputs(
            rng, n=240, n_entities=6)
        walks = []
        live_arrays = jax.live_arrays
        monkeypatch.setattr(
            jax, "live_arrays",
            lambda *a, **k: walks.append(1) or live_arrays(*a, **k))

        def sweep():
            return run_coordinate_descent(
                coords, 1, TaskType.LOGISTIC_REGRESSION, labels, weights,
                offsets)

        obs_compile.arm()
        tracer = trace.get_tracer()
        assert tracer is not None
        sweep()  # compile everything at these shapes OUTSIDE the guard
        tracer.drain()
        cd.reset_hot_loop_stats()
        sync_telemetry.reset_host_fetches()
        with jax.transfer_guard_device_to_host("disallow"):
            res = sweep()
        assert len(res.states) == len(coords)
        # an update's one epilogue fetch and its tracker's explicit drain
        assert sync_telemetry.host_fetch_count() == 2 * len(coords)
        assert walks == []
        names = collections.Counter(e["name"] for e in tracer.events())
        assert names["cd.sweep"] == 1
        assert names["cd.dispatch"] == names["cd.epilogue_fetch"] \
            == len(coords)
        assert names["re.offsets"] == names["re.score"] == 1
        assert names["cd.hbm_sample"] == 0
        devicemem.watch_sweeps(True)
        try:
            sweep()
        finally:
            devicemem.watch_sweeps(False)
        assert len(walks) == 2
        assert [e["name"] for e in tracer.events()].count(
            "cd.hbm_sample") == 1

    def test_observed_runs_watch_their_sweeps_and_stop_at_finish(
            self, tmp_path):
        assert not devicemem.sweeps_watched()
        run = start_observed_run(str(tmp_path), heartbeat_seconds=3600)
        try:
            assert devicemem.sweeps_watched()
        finally:
            run.finish()
        assert not devicemem.sweeps_watched()

    def test_armed_records_count_updates_not_solver_iterations(
            self, rng, registry):
        """What the armed device plane costs the host is records: per
        update one ``cd.hbm_watermark`` mark beside the tracer's own
        spans, per (site, coordinate) one metric series. Both are a fixed
        function of sweeps and coordinates — the same on the second warm
        run as on the first, twice the spans for two sweeps, no new
        series, and the same whether the solvers run 3 iterations or 20.
        The siblings above hold the zero-retrace and zero-sync halves;
        the share of a sweep's time was read on the chip (PERF.md
        section 6, PR 27)."""
        import collections

        import test_obs

        long_solves, short_solves, *arrays = test_obs._two_budgets(rng)
        obs_compile.arm(registry=registry)
        devicemem.arm(registry=registry)

        def span_counts(coords, sweeps):
            return test_obs._span_counts(coords, sweeps, *arrays)

        def series():
            return sorted((r["name"], sorted(r["labels"].items()))
                          for r in registry.snapshot())

        # every signature the counted runs will meet compiles here
        span_counts(long_solves, 2)
        span_counts(short_solves, 1)
        first = span_counts(long_solves, 1)
        series_after_first = series()
        n_coords = len(long_solves)
        assert first["cd.update"] == n_coords
        assert first["cd.hbm_watermark"] == n_coords
        assert span_counts(long_solves, 1) == first
        assert span_counts(short_solves, 1) == first
        assert span_counts(long_solves, 2) == collections.Counter(
            {name: 2 * n for name, n in first.items()})
        assert series() == series_after_first


# -- the program's spans in the profiler's trace -------------------------------


def _host_events(trace_dir):
    """{name: [(start_ns, end_ns, stats)]} of the profiler capture's host
    plane, as ``jax.profiler.ProfileData`` reads the ``.xplane.pb``."""
    import glob

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return events


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


class TestSpansInTheProfilerTrace:
    def test_disarmed_and_untraced_span_is_the_shared_noop(self):
        assert trace.span("cd.sweep", sweep=0) is trace._NULL_SPAN
        obs_compile.arm()
        armed = trace.span("cd.sweep", sweep=0)
        assert armed is not trace._NULL_SPAN
        with armed:  # no profiler session, no tracer: still a context
            pass
        obs_compile.disarm()
        assert trace.span("cd.sweep", sweep=0) is trace._NULL_SPAN

    def test_armed_cd_run_lands_on_the_host_plane(self, rng, registry,
                                                  tmp_path):
        """A CPU profiler capture of a tiny armed run: the sweep loop's
        spans are on the host plane with their labels as stats, nested as
        the code nests them, a block's dispatch and its later fetch joined
        by ``update``, and a compile where it happened, at its real
        length."""
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import TaskType

        coords, labels, weights, offsets = _cd_inputs(
            rng, n=240, n_entities=6)
        obs_compile.arm(registry=registry)
        jax.profiler.start_trace(str(tmp_path))
        try:
            run_coordinate_descent(coords, 1, TaskType.LOGISTIC_REGRESSION,
                                   labels, weights, offsets)
        finally:
            jax.profiler.stop_trace()
        events = _host_events(str(tmp_path))

        (sweep,) = events["cd.sweep"]
        assert sweep[2] == {"sweep": 0}
        dispatches = events["cd.dispatch"]
        fetches = events["cd.epilogue_fetch"]
        assert len(dispatches) == len(fetches) == len(coords)
        for span in dispatches + fetches:
            assert _inside(span, sweep)
        # dispatch -> fetch, joined by the update serial
        by_update = {d[2]["update"]: d for d in dispatches}
        assert sorted(by_update) == list(range(len(coords)))
        for fetch in fetches:
            dispatch = by_update[fetch[2]["update"]]
            assert dispatch[1] <= fetch[0]
            assert fetch[2]["coordinate"] == dispatch[2]["coordinates"]
        for wait in events.get("cd.pipeline_wait", []):
            assert any(_inside(f, wait) and f[2]["update"]
                       == wait[2]["update"] for f in fetches)
        # the solves sit inside their coordinate's dispatch
        (solve,) = events["optimizer.solve"]
        assert solve[2]["optimizer"] == "LBFGS"
        fixed = next(d for d in dispatches
                     if d[2]["coordinates"] == "fixed")
        assert _inside(solve, fixed)
        (re_solve,) = events["re.solve"]
        assert re_solve[2]["solver"] == "lbfgs"
        assert any(_inside(re_solve, d) for d in dispatches)
        # the solver's compile: inside optimizer.solve, as long as the
        # counter says, its lowering a child of it
        compiles = [c for c in events["xla.compile"]
                    if c[2]["site"] == "optimizer.lbfgs"]
        (compiled,) = compiles
        assert _inside(compiled, solve)
        secs = registry.counter("compile_secs").value(
            site="optimizer.lbfgs")
        # (the span also holds the cost analysis the labels wait for)
        assert (compiled[1] - compiled[0]) / 1e9 >= secs > 0
        (lowered,) = [e for e in events["xla.lower"]
                      if e[2]["site"] == "optimizer.lbfgs"]
        assert _inside(lowered, compiled)
        lower_secs = registry.counter("lower_secs").value(
            site="optimizer.lbfgs")
        assert 0 < lower_secs <= secs
        assert (lowered[1] - lowered[0]) / 1e9 == pytest.approx(
            lower_secs, rel=0.05, abs=2e-4)

    def test_compile_span_is_real_in_the_tracer_too(self, registry):
        """The program's own trace: ``xla.compile`` has a duration, its
        late labels, and ``xla.lower`` / ``xla.retrace`` inside it."""
        obs_compile.arm(registry=registry)
        tracer = trace.enable()
        f = jax.jit(lambda x: (x * 2.0).sum())
        obs_compile.call("t.span", f, (jnp.ones((8,), jnp.float32),),
                         arg_names=("x",))
        obs_compile.call("t.span", f, (jnp.ones((9,), jnp.float32),),
                         arg_names=("x",))
        spans = tracer.events()
        compiles = [e for e in spans if e["name"] == "xla.compile"]
        lowers = [e for e in spans if e["name"] == "xla.lower"]
        (retrace,) = [e for e in spans if e["name"] == "xla.retrace"]
        assert len(compiles) == len(lowers) == 2
        for compiled, lowered in zip(compiles, lowers):
            assert compiled["dur_us"] / 1e6 >= compiled["labels"]["secs"] > 0
            assert lowered["depth"] == compiled["depth"] + 1
            assert compiled["ts_us"] <= lowered["ts_us"]
            assert lowered["ts_us"] + lowered["dur_us"] \
                <= compiled["ts_us"] + compiled["dur_us"]
        assert retrace["labels"]["field"] == "shape"
        assert compiles[1]["ts_us"] <= retrace["ts_us"] \
            <= compiles[1]["ts_us"] + compiles[1]["dur_us"]
        assert registry.counter("lower_secs").value(site="t.span") \
            <= registry.counter("compile_secs").value(site="t.span")


# -- HBM accounting ----------------------------------------------------------


class TestDeviceMem:
    def test_disarmed_noops(self, registry):
        assert devicemem.sample(registry=registry) == 0
        devicemem.note_coordinate("c")
        assert devicemem.drain_coordinate_watermarks(0,
                                                     registry=registry) == {}
        assert registry.gauge("hbm_bytes").records() == []

    def test_sample_sets_gauges_and_peak(self, registry):
        devicemem.arm(registry=registry)
        keep = jnp.ones((256, 256), jnp.float32)  # noqa: F841
        total = devicemem.sample()
        assert total > 0
        records = registry.gauge("hbm_bytes").records()
        assert records, "no hbm_bytes gauge set by sample()"
        for r in records:
            assert set(r["labels"]) == {"device", "kind"}
        assert devicemem.peak_bytes() >= total

    def test_coordinate_watermarks_drain_and_clear(self, registry):
        devicemem.arm(registry=registry)
        tracer = trace.enable()
        keep = jnp.ones((128, 128), jnp.float32)  # noqa: F841
        devicemem.note_coordinate("fixed")
        devicemem.note_coordinate("per-user")
        drained = devicemem.drain_coordinate_watermarks(3,
                                                        registry=registry)
        assert set(drained) == {"fixed", "per-user"}
        assert all(v > 0 for v in drained.values())
        marks = {r["labels"]["coordinate"]: r["value"]
                 for r in registry.gauge("hbm_watermark_bytes").records()}
        assert marks == drained
        spans = [e for e in tracer.events()
                 if e["name"] == "cd.hbm_watermark"]
        assert {e["labels"]["coordinate"] for e in spans} == set(drained)
        assert all(e["labels"]["sweep"] == 3 for e in spans)
        # the drain clears the map: a second drain is empty
        assert devicemem.drain_coordinate_watermarks(4,
                                                     registry=registry) == {}


# -- ObservedRun integration -------------------------------------------------


class TestObservedRunDeviceTelemetry:
    def test_run_end_carries_peak_hbm_bytes(self, tmp_path):
        registry = MetricsRegistry()
        run = start_observed_run(str(tmp_path), heartbeat_seconds=60,
                                 registry=registry, device_telemetry=True)
        assert obs_compile.is_armed() and devicemem.armed()
        keep = jnp.ones((64, 64), jnp.float32)  # noqa: F841
        run.finish()
        assert not obs_compile.is_armed() and not devicemem.armed()
        run_end = None
        with open(os.path.join(tmp_path, "metrics.jsonl")) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("kind") == "run_end":
                    run_end = rec
        assert run_end is not None
        assert run_end["peak_hbm_bytes"] > 0

    def test_flag_requires_trace_dir(self):
        class NS:
            trace_dir = None
            telemetry_endpoint = None
            device_telemetry = True

        with pytest.raises(ValueError, match="--device-telemetry "
                                             "requires --trace-dir"):
            start_observed_run_from_flags(NS())
