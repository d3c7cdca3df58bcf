"""Share of the memory roofline: the least seconds the chip could take to
read what the traced steps' passes must read (``work.py``) at the peak HBM
bandwidth, over the seconds the device was busy in those steps. Bound by
bytes: two matrix-vector products a pass are far under the FLOP roof."""


def read(entry: dict, context: dict):
    trace, work = context["trace"], context["traced_work"]
    if not trace or not work or trace["busy_s"] <= 0:
        return None
    least = work["bytes"] / context["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (trace["busy_s"] * trace["chips"])
