"""``module_time`` with a choice of modules that may be empty: device
milliseconds per unit of work (a sweep) in the XLA modules of the traced
steps whose names match ``pattern`` (every module where there is none) and
match none of the patterns listed under ``except``.

``None`` where no module is chosen, which ``module_time`` takes for a name
that has moved and raises on: a metric of a module that only some commits
have (the parent of the PR that named it) is then left out of that run's
line and the run does not fail. A metric that reads "the rest" (``except``
alone) is the guard that the named layers add up to the device's busy time.
"""

import re

from benchmark import trace_reduce


def chosen_seconds(modules: list, pattern=None, excepted=()):
    """Device seconds in the modules chosen as above; ``None`` where none
    is."""
    want = re.compile(pattern) if pattern else None
    skip = [re.compile(p) for p in excepted]
    found = [seconds for name, seconds
             in trace_reduce.seconds_by_module(modules).items()
             if (want is None or want.search(name))
             and not any(rx.search(name) for rx in skip)]
    return sum(found) if found else None


def read(entry: dict, context: dict):
    trace = context["trace"]
    if not trace or not trace["steps"] or not trace["modules"]:
        return None
    seconds = chosen_seconds(trace["modules"], entry.get("pattern"),
                             entry.get("except", ()))
    if seconds is None:
        return None
    return 1e3 * seconds / trace["chips"] / (
        trace["steps"] * context["units_per_step"])
