"""A count the step's result carries (``field``, a number or a list of
numbers in every step's record), summed, per unit of work."""


def read(entry: dict, context: dict):
    total, seen = 0.0, False
    for record in context["records"]:
        value = record.get(entry["field"])
        if value is None:
            continue
        seen = True
        total += sum(value) if isinstance(value, (list, tuple)) else value
    if not seen or not context["units"]:
        return None
    return total / context["units"]
