"""Seconds of the benchmark's own host clock around the set-up phases named
in ``spans`` (summed)."""


def read(entry: dict, context: dict):
    found = [context["host_spans"][name] for name in entry["spans"]
             if name in context["host_spans"]]
    return sum(found) if found else None
