"""PR 37's metrics of the sweep loop and the block build: the two readers on
hand-built spans and module events, the metrics' files, and both sweep cells
at a tiny size on the CPU, where the span and counter metrics print and the
three device ones are left out."""

import time

import pytest

from benchmark import harness
from benchmark.readers import module_time_where, span_time
from tests.bench_harness import tiny
from tests.bench_harness.test_game_cells import _tiny_config

GLMIX = "glmix-ml10m.train"
GAME = "game-ml20m.train"
SPAN_METRICS = ("dispatch_ms.sweep", "wait_ms.sweep")
DEVICE_METRICS = ("score_ms.sweep", "offsets_ms.sweep", "sweep_rest_ms.sweep")
BUILD_METRICS = tuple(f"build_{stage}_s" for stage in (
    "group", "project", "pack", "passive", "transfer", "fixed"))


def _span(name, ts_us, dur_us, tid=1):
    return {"name": name, "tid": tid, "depth": 0, "ts_us": float(ts_us),
            "dur_us": float(dur_us), "labels": {}}


def _sweep(at_us, dispatch_us, fetch_us, tid=1):
    """One ``cd.sweep`` of two updates: each a dispatch and a fetch, then
    the drain; 10 us of the loop's own between them."""
    events, t = [], at_us + 10
    for _ in range(2):
        events.append(_span("cd.dispatch", t, dispatch_us, tid))
        t += dispatch_us + 10
        # the fetch of a pipelined update lies inside its wait
        events.append(_span("cd.pipeline_wait", t, fetch_us + 2, tid))
        events.append(_span("cd.epilogue_fetch", t + 1, fetch_us, tid))
        t += fetch_us + 12
    events.append(_span("cd.tracker_drain", t, 5, tid))
    events.append(_span("cd.sweep", at_us, t + 15 - at_us, tid))
    return events


def test_span_time_sums_inside_the_last_steps_and_takes_the_median():
    warmup = _sweep(0, 900_000, 100)  # it compiled inside its dispatches
    window = (_sweep(3_000_000, 1_000, 40_000)
              + _sweep(4_000_000, 1_200, 39_000)
              + _sweep(5_000_000, 30_000, 41_000)  # the traced, slow step
              + _sweep(6_000_000, 1_100, 40_500))
    other_thread = [_span("cd.dispatch", 3_000_050, 777, tid=2)]
    events = warmup + window + other_thread
    sums = span_time.inside(events, ["cd.dispatch"], "cd.sweep", 4)
    assert sums == [2_000.0, 2_400.0, 60_000.0, 2_200.0]
    # the warm-up step is before the last four; the slow one is not the median
    assert span_time.inside(events, ["cd.dispatch"], "cd.sweep", 5)[0] \
        == 1_800_000.0
    waits = span_time.inside(
        events, ["cd.epilogue_fetch", "cd.tracker_drain"], "cd.sweep", 4)
    assert waits == [80_005.0, 78_005.0, 82_005.0, 81_005.0]


def test_span_time_with_fewer_sweeps_than_steps_reads_nothing():
    events = _sweep(0, 1_000, 40_000)
    assert span_time.inside(events, ["cd.dispatch"], "cd.sweep", 2) is None
    assert span_time.inside(events, ["cd.dispatch"], "cd.sweep", 0) is None
    assert span_time.inside([], ["cd.dispatch"], "cd.sweep", 1) is None
    # a sweep in which no such span closed is a 0 of its own, not nothing
    assert span_time.inside(events, ["cd.never"], "cd.sweep", 1) == [0]


def test_span_time_reads_the_programs_store_and_only_while_it_keeps_one():
    from photon_ml_tpu.obs import compile as obs_compile
    from photon_ml_tpu.obs import trace

    entry = {"spans": ["t.inner"], "within": "t.outer"}
    context = {"records": [{}, {}, {}], "units_per_step": 1.0}
    obs_compile.disarm()
    trace.disable()
    assert span_time.read(entry, context) is None  # not armed: no store
    obs_compile.arm()
    try:
        for pause in (0.001, 0.02, 0.001):
            with trace.span("t.outer"):
                with trace.span("t.inner"):
                    time.sleep(pause)
        got = span_time.read(entry, context)
        assert 1.0 <= got < 15.0  # the median, in ms: not the 20 ms one
        assert span_time.read(entry, dict(context, records=[{}] * 4)) is None
        assert span_time.read(entry, dict(context, units_per_step=2.0)) \
            == pytest.approx(got / 2)
    finally:
        obs_compile.disarm()
    assert trace.get_tracer() is None


MODULES = [(0, 3_000_000, "jit__minimize_lbfgs_impl(11)"),
           (0, 7_000_000, "jit__fit_blocks_impl(13)"),
           (0, 7_000_000, "jit__fit_blocks_impl(14)"),
           (0, 400_000, "jit__active_margins(15)"),
           (0, 600_000, "jit__gather_scores(16)"),
           (0, 100_000, "jit__bucket_coefs(17)"),
           (0, 500_000, "jit__block_offsets(18)"),
           (0, 20_000, "jit_subtract(19)"),
           (0, 30_000, "jit__factored_latent_blocks(20)")]


def _context(modules, steps=2):
    return {"trace": {"steps": steps, "chips": 1, "modules": modules},
            "units_per_step": 1.0}


def test_module_time_where_by_pattern_by_exception_and_nothing_as_none():
    layer = harness.load_spec(GAME).layer_metrics
    context = _context(MODULES)
    read = module_time_where.read
    assert read(layer["offsets_ms.sweep"], context) == pytest.approx(0.25)
    # the rest: the subtract and the latent blocks, over two steps
    assert read(layer["sweep_rest_ms.sweep"], context) \
        == pytest.approx(0.025)
    assert read({}, context) == pytest.approx(1e-6 * sum(
        d for _, d, _ in MODULES) / 2)
    # the parent of the PR that named the offset exchange: its gathers are
    # in the rest, and the metric of the name it lacks is left out
    parent = [m for m in MODULES if "block_offsets" not in m[2]] \
        + [(0, 480_000, "jit_gather(21)")]
    assert read(layer["offsets_ms.sweep"], _context(parent)) is None
    assert read(layer["sweep_rest_ms.sweep"], _context(parent)) \
        == pytest.approx(0.265)
    # every module named by a layer: no rest to report
    named = [m for m in MODULES if m[2].startswith(
        ("jit__fit", "jit__min", "jit__gather"))]
    assert read(layer["sweep_rest_ms.sweep"], _context(named)) is None
    for nothing in ({"trace": None, "units_per_step": 1.0},
                    _context(MODULES, steps=0), _context([])):
        assert read(layer["offsets_ms.sweep"], nothing) is None


def test_the_layers_metrics_take_every_module_once():
    """Solves, refit, scoring, offsets and the rest add up to the modules'
    device time: the guard the rest is for."""
    from benchmark.readers import module_time

    layer = harness.load_spec(GAME).layer_metrics
    context = _context(MODULES, steps=1)
    parts = [module_time.read(layer[name], context) for name in (
        "fe_solve_ms.sweep", "re_solve_ms.sweep", "score_ms.sweep")]
    parts += [module_time_where.read(layer[name], context) for name in (
        "offsets_ms.sweep", "sweep_rest_ms.sweep")]
    assert sum(parts) == pytest.approx(1e-6 * sum(d for _, d, _ in MODULES))
    assert parts[2] == pytest.approx(1.1)  # the margins, the gather, the cut


def test_the_new_metrics_files_and_the_cells_lists():
    for cell in (GLMIX, GAME):
        layer = harness.load_spec(cell).layer_metrics
        for name in SPAN_METRICS + DEVICE_METRICS:
            assert layer[name]["layer"] == "sweep loop"
            assert layer[name]["moves"] == "sweep_s"
        for name in SPAN_METRICS:
            assert layer[name]["reader"] == "span_time"
            assert layer[name]["within"] == "cd.sweep"
        # every update has a fetch; the wait only encloses a pipelined one
        assert "cd.pipeline_wait" not in layer["wait_ms.sweep"]["spans"]
        for name in BUILD_METRICS:
            assert layer[name]["counter"] == "block_build_secs"
            assert layer[name]["labels"] == {
                "stage": name[len("build_"):-len("_s")]}
            assert layer[name]["layer"] == layer["block_build_s"]["layer"]
            assert layer[name]["moves"] == "setup_s"
        # no new metric points the reader that raises at a module the
        # parent lacks
        assert layer["score_ms.sweep"]["reader"] == "module_time"
        assert "block_offsets" not in layer["score_ms.sweep"]["pattern"]
        assert set(layer["sweep_rest_ms.sweep"]["except"]) >= {
            layer[name]["pattern"] for name in (
                "fe_solve_ms.sweep", "re_solve_ms.sweep", "score_ms.sweep",
                "offsets_ms.sweep")}
    assert harness.load_spec(GAME).layer_metrics["mf_refit_ms.sweep"][
        "pattern"] in harness.load_spec(GAME).layer_metrics[
            "sweep_rest_ms.sweep"]["except"]


def _game_spec():
    full = harness.load_spec(GAME)
    return full._replace(config=_tiny_config(full.config))


@pytest.mark.parametrize("cell", [GLMIX, GAME])
def test_a_tiny_sweep_cell_reports_the_span_and_counter_metrics(cell,
                                                                tmp_path):
    from photon_ml_tpu.obs.metrics import REGISTRY

    # the counter is the process's: a run is a process of its own, a test
    # is not, so what earlier rehearsals booked is taken out
    REGISTRY.counter("block_build_secs").reset()
    trace_dir = str(tmp_path / "trace")
    if cell == GLMIX:
        result = tiny.run(cell, trace=True, trace_dir=trace_dir)
    else:
        result = harness.run_cell(_game_spec(), 2**31 + 77, 0.3, True,
                                  time.perf_counter(), tiny.DEVICE,
                                  trace_dir=trace_dir)
    assert result["correct"] is True, result["checks"]
    value = {n: m["value"] for n, m in result["metrics"].items()}
    unit = {n: m["unit"] for n, m in result["metrics"].items()}
    for name in SPAN_METRICS + BUILD_METRICS:
        assert isinstance(value[name], float) and value[name] > 0.0, name
    assert {unit[n] for n in SPAN_METRICS} == {"ms"}
    assert {unit[n] for n in BUILD_METRICS} == {"s"}
    # no device plane on the CPU: nothing to read is left out, never 0
    assert not set(DEVICE_METRICS) & set(value)
    # the stages lie inside the benchmark's own clock around the build
    assert sum(value[n] for n in BUILD_METRICS) < value["block_build_s"]
