"""A ratio of the program's always-on counters, read from its registry in
this process when the metric is computed: the total of ``counter`` over the
label sets that carry every pair of ``labels`` (all of them where none is
given), over the total of ``per`` under the same filter where given, times
``scale``. ``None`` where the counter was never written under those labels
or the denominator is 0: nothing read is never written as 0, and a program
that has no such counter (the parent of the PR that adds it) reports no
such metric.

The totals are over the process, warm-up steps included. The warm-up steps
are the window's own call on the same data and nothing compiles inside a
window, so a ratio over all steps is the window's ratio, and a compile
counter's total is its value at the window's start.
"""


def total(name: str, labels: dict):
    """Sum of counter ``name`` over the label sets matching ``labels``;
    ``None`` where no such label set was ever written."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    wanted = set(labels.items())
    found = [value for key, value in REGISTRY.counter(name).items().items()
             if wanted <= set(key)]
    return float(sum(found)) if found else None


def read(entry: dict, context: dict):
    labels = entry.get("labels", {})
    value = total(entry["counter"], labels)
    if value is None:
        return None
    if "per" in entry:
        base = total(entry["per"], labels)
        if not base:
            return None
        value /= base
    return float(value * entry.get("scale", 1))
