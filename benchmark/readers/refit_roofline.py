"""The projection refit's share of the memory roofline: the least seconds
the chip could take to move what the refit's passes must move
(``work_game.refit_work``, carried by every step's record under ``field``)
at the peak HBM bandwidth, over the device seconds of the XLA modules whose
names match ``pattern`` in the traced steps. Every step of the cell does the
same work, so the traced steps are credited the records' mean. ``None``
where no record carries the field, there is no trace, or no module ran."""

from benchmark import trace_reduce


def read(entry: dict, context: dict):
    trace = context["trace"]
    found = [r[entry["field"]]["bytes"] for r in context["records"]
             if r.get(entry["field"])]
    if not trace or not trace["steps"] or not trace["modules"] or not found:
        return None
    seconds = trace_reduce.module_seconds_matching(
        trace["modules"], entry["pattern"])
    if seconds <= 0:
        return None
    least = (sum(found) / len(found)) * trace["steps"] \
        / context["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
