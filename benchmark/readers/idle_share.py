"""Share of the traced steps' span in which no operation ran on the device:
100 * (1 - union of the device's operation intervals / span)."""


def read(entry: dict, context: dict):
    trace = context["trace"]
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
