"""The data-driven harness: one cell, once.

``BENCHMARK.json`` names the cell; ``configs/<config>.json``,
``workloads/<cell>.json`` and every ``layer_metrics/*.json`` that lists the
cell say the rest. A workload names its step by ``kind``
(``kinds/<kind>.py``) and a layer metric its reduction by ``reader``
(``readers/<reader>.py``): both are found by name, so a later PR adds a
cell, a metric, a kind or a reader as files of its own (README.md).

Nothing here asks for a chip: ``run.py``'s ``main`` does, and the CPU tests
call :func:`run_cell` at tiny sizes.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import NamedTuple, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_ANNOTATION = "bench.step"


class Spec(NamedTuple):
    bench: dict  # BENCHMARK.json
    cell: dict  # its entry of "workloads"
    config: dict  # configs/<config>.json
    workload: dict  # workloads/<cell>.json
    layer_metrics: dict  # name -> layer_metrics/<name>.json, this cell's


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(cell_name: str, root: str = ROOT) -> Spec:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[cell["config"]]["file"]))
    workload = _read_json(os.path.join(
        root, "benchmark", "workloads", cell_name + ".json"))
    layer_metrics = {}
    for entry in bench["per_layer"]:
        if cell_name in entry.get("workloads", [cell_name]):
            layer_metrics[entry["name"]] = _read_json(os.path.join(
                root, "benchmark", "layer_metrics", entry["name"] + ".json"))
    return Spec(bench, cell, config, workload, layer_metrics)


def load_kind(name: str):
    return importlib.import_module(f"benchmark.kinds.{name}")


def load_reader(name: str):
    return importlib.import_module(f"benchmark.readers.{name}")


def end_to_end_names(spec: Spec) -> list:
    name = spec.cell["name"]
    return [m["name"] for m in spec.bench["end_to_end"]
            if name in m.get("workloads", [name])]


def units_of(spec: Spec) -> dict:
    return {m["name"]: m["unit"]
            for m in spec.bench["end_to_end"] + spec.bench["per_layer"]}


class Phases:
    """Host clocks around the phases of set-up, each also written into the
    profiler's trace as a ``TraceAnnotation`` where one is running."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + self.clock() - t0


def say(msg: str) -> None:
    print(msg, flush=True)


def run_window(kind, state, seconds: float, trace_dir: Optional[str],
               traced_steps: int, clock=time.perf_counter,
               steps_per_cycle: int = 1) -> dict:
    """Whole steps until ``seconds`` have passed; the step in flight is
    finished, and where the cell's steps come in cycles of
    ``steps_per_cycle`` different ones, the cycle in flight, so that every
    window holds the same steps in the same numbers. With ``trace_dir``,
    the profiler covers ``traced_steps`` whole steps inside the window,
    after the first. A step ends in a fetched host value, so no timing here
    measures an enqueue."""
    import jax

    records, failed = [], 0
    traced = None  # {"first": index of the first traced record, "steps": n}
    tracing = False
    t0 = clock()
    while True:
        index = len(records) + failed
        if trace_dir and traced is None and index >= 1:
            jax.profiler.start_trace(trace_dir)
            tracing, traced = True, {"first": len(records), "steps": 0}
        try:
            with jax.profiler.TraceAnnotation(STEP_ANNOTATION):
                records.append(kind.step(state))
            if tracing:
                traced["steps"] += 1
        except Exception as exc:  # a step that fails is counted, not hidden
            failed += 1
            print(f"step {index} failed: {exc!r}", file=sys.stderr)
            if failed >= 3:
                break
        if tracing and traced["steps"] >= traced_steps:
            jax.profiler.stop_trace()
            tracing = False
        if clock() - t0 >= seconds and not tracing and (
                failed or len(records) % steps_per_cycle == 0):
            break
    t1 = clock()
    return {"records": records, "failed": failed, "window_s": t1 - t0,
            "traced": traced or {"first": 0, "steps": 0}}


def judge(checks: list) -> bool:
    """``checks`` is [(name, value, limit)]: correct when every value is a
    number no larger than its limit."""
    return bool(checks) and all(
        value == value and value <= limit for _, value, limit in checks)


def reduce_trace(trace_dir: str, traced: dict, chips: int) -> dict:
    """What the readers and the last line need from the trace."""
    from benchmark import trace_reduce as tr

    trace = tr.load(trace_dir)
    devices = trace.devices[:chips] if trace.devices else []
    steps = [e for e in trace.host if e[2] == STEP_ANNOTATION]
    all_ops = [e for d in devices for e in d.ops]
    if steps:
        lo = min(s for s, _, _ in steps)
        hi = max(s + d for s, d, _ in steps)
    elif all_ops:
        lo = min(s for s, _, _ in all_ops)
        hi = max(s + d for s, d, _ in all_ops)
    else:
        lo = hi = 0
    busy = [tr.union_seconds(tr.clip(d.ops, lo, hi)) for d in devices]
    ops0 = tr.clip(devices[0].ops, lo, hi) if devices else []
    modules = [e for d in devices for e in tr.clip(d.modules, lo, hi)]
    host = [e for e in trace.host if e[2] != STEP_ANNOTATION]
    gaps = tr.idle_gaps(ops0, lo, hi)[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "modules": modules, "chips": max(len(devices), 1),
        "steps": traced["steps"],
        "breakdown": {
            "device_ops": tr.top(tr.self_seconds_by_name(ops0)),
            "idle_gaps": [list(g) for g in tr.label_gaps(gaps, host)]},
    }


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool,
             process_start: float, device: dict, devices=(),
             clock=time.perf_counter, trace_dir: Optional[str] = None
             ) -> dict:
    """Set-up, warm-up, the measured window, the comparison with the plain
    reference, and the reduction to the last line's object."""
    from benchmark import program, work

    kind = load_kind(spec.workload["kind"])
    phases = Phases(clock)
    program.arm_compile_counters()
    state = kind.build(spec.config, spec.workload, seed, phases)
    for line in kind.describe(state):
        say(line)
    with phases("warmup"):
        # the window's own call, from the seed, on the object it will drive
        for _ in range(int(spec.workload.get("warmup_steps", 1))):
            first = kind.step(state)
    at_start = program.counters()
    setup_s = clock() - process_start

    if trace and trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = run_window(kind, state, seconds, trace_dir if trace else None,
                        int(spec.workload.get("traced_steps", 1)), clock,
                        int(spec.workload.get("steps_per_cycle", 1)))
    at_end = program.counters()
    records = window["records"]
    memory_peak = program.memory_peak_bytes(devices)
    say(json.dumps({
        "window_s": window["window_s"], "steps": len(records),
        "compiles_in_window": at_end["compiles"] - at_start["compiles"],
        "compile_secs_at_start": at_start["compile_secs"],
        "peak_bytes_in_use": memory_peak,
        "iterations_first_step": first.get("iterations"),
        "iterations_last_step": records[-1].get("iterations")
        if records else None,
        "setup_phases_s": phases.seconds}))

    units_per_step = float(spec.workload["units_per_step"])
    units = units_per_step * len(records)
    metric = spec.workload["metric"]
    values = {"setup_s": setup_s}
    if units:
        values[metric] = window["window_s"] / units

    reduced = None
    if trace and trace_dir and window["traced"]["steps"]:
        t0 = clock()
        reduced = reduce_trace(trace_dir, window["traced"],
                               int(spec.cell["chips"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(json.dumps({"trace_reduce_s": clock() - t0}))

    # the comparison: after the window, after the peak was read, with the
    # program's state freed
    checks = []
    if records:
        total_work = work.add_work(*[kind.work(state, r) for r in records])
        traced = window["traced"]
        traced_records = records[
            traced["first"]:traced["first"] + traced["steps"]]
        traced_work = work.add_work(
            *[kind.work(state, r) for r in traced_records]) \
            if traced_records else None
        kind.release(state)
        gc.collect()
        t0 = clock()
        checks = kind.verify(state, records[-1], spec.workload["limits"])
        say(json.dumps({"reference_s": clock() - t0}))
    correct = judge(checks) and window["failed"] == 0

    if trace:
        context = {
            "trace": reduced, "window_s": window["window_s"],
            "records": records, "units": units,
            "units_per_step": units_per_step,
            "work": total_work if records else None,
            "traced_work": traced_work if records else None,
            "host_spans": phases.seconds, "counters": at_start,
            "peaks": work.peaks(device["kind"]),
            "chips": int(spec.cell["chips"])}
        values = {}
        for name, entry in spec.layer_metrics.items():
            got = load_reader(entry["reader"]).read(entry, context)
            if got is not None:
                values[name] = got

    unit = units_of(spec)
    wanted = list(spec.layer_metrics) if trace else end_to_end_names(spec)
    device = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct,
              "attempted": len(records) + window["failed"],
              "failed": window["failed"],
              "metrics": {n: {"value": values[n], "unit": unit[n]}
                          for n in wanted if n in values},
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result


def print_result(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error; the object as the last line of standard output."""
    sys.stdout.flush()
    for name, pair in result["checks"].items():
        verdict = "ok" if pair["value"] <= pair["limit"] else "OVER"
        print(f"check {name}: {pair['value']!r} limit {pair['limit']!r} "
              f"{verdict}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
