"""Host milliseconds per unit of work inside the program's own spans named
in ``spans``, read from the program's span store in this process when the
metric is computed, as ``program_counter`` reads its registry.

While the program is armed (``program.arm_compile_counters``: every run) it
keeps the newest closed spans (``photon_ml_tpu/obs/trace.py``). This reader
takes the last ``len(records)`` closed spans named ``within`` (one a step:
the window's, since nothing between the window's end and the readers closes
one), sums for each the named spans that lie inside it on the same thread
(the names of one metric must not nest inside one another, or a stretch is
counted twice), and returns the **median** of those sums. The median leaves
out the one step the profiler traced (its Python tracer slows host code)
and any step that met a hiccup of the host; the warm-up steps, which
compile inside a span, are before the window and never among the last.

``None`` where the program keeps no spans (the parent of the PR that made
it keep them; a program that is not armed) or kept fewer ``within`` spans
than the window has steps: nothing read is never written as 0.
"""

import statistics


def inside(events: list, spans: list, within: str, steps: int):
    """Per ``within`` span of the last ``steps``, the microseconds of the
    events named in ``spans`` inside it on its thread; ``None`` where
    ``events`` holds fewer ``within`` spans than ``steps``. ``events`` are
    the store's dicts: ``name``, ``tid``, ``ts_us``, ``dur_us``."""
    outer = [e for e in events if e["name"] == within]
    if not steps or len(outer) < steps:
        return None
    wanted = [e for e in events if e["name"] in spans]
    sums = []
    for o in sorted(outer, key=lambda e: e["ts_us"])[-steps:]:
        lo, hi = o["ts_us"], o["ts_us"] + o["dur_us"]
        sums.append(sum(
            e["dur_us"] for e in wanted
            if e["tid"] == o["tid"] and lo <= e["ts_us"]
            and e["ts_us"] + e["dur_us"] <= hi))
    return sums


def read(entry: dict, context: dict):
    from photon_ml_tpu.obs import trace

    tracer = trace.get_tracer()
    if tracer is None:
        return None
    sums = inside(tracer.events(), entry["spans"], entry["within"],
                  len(context["records"]))
    if sums is None or not context["units_per_step"]:
        return None
    return statistics.median(sums) / 1e3 / context["units_per_step"]
