"""A counter of the program, read at the window's start."""


def read(entry: dict, context: dict):
    return context["counters"].get(entry["counter"])
