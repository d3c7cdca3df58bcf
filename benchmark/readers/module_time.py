"""Device milliseconds per unit of work (a fit, a sweep) in the XLA modules
whose names match ``pattern``, over the traced steps."""

from benchmark import trace_reduce


def read(entry: dict, context: dict):
    trace = context["trace"]
    if not trace or not trace["steps"]:
        return None
    if not trace["modules"]:  # no device plane: nothing to read
        return None
    seconds = trace_reduce.module_seconds_matching(
        trace["modules"], entry["pattern"]) / trace["chips"]
    if seconds <= 0:  # modules ran, none of that name: the name has moved
        raise LookupError(
            f"no XLA module of the traced steps matches {entry['pattern']!r}"
            f" (ran: {sorted({e[2] for e in trace['modules']})[:8]})")
    return 1e3 * seconds / (trace["steps"] * context["units_per_step"])
