"""Compile/retrace attribution: the device plane's "why did XLA build
an executable" half of the observability layer.

The package's hot jitted entry points (the CD fused epilogue, the
random-effect block dispatch, the three fixed-effect solvers) route
their calls through :func:`call`, a site-labeled indirection that is a
plain passthrough while disarmed (one module-global check — the
default, so nothing here costs the untraced hot path anything) and,
when armed via ``--device-telemetry``:

- keys each call on the site's *abstract signature* (array shapes /
  dtypes / weak types, pytree structure, static values, function
  identities — the same things jax's dispatch cache keys on),
- on a signature never seen at that site, runs the compile explicitly
  via the AOT API (``fn.lower(*args)``, then ``.compile()``) inside an
  ``xla.compile`` span with an ``xla.lower`` child, records
  ``compiles{site}``, ``compile_secs{site}`` (the whole interval) and
  ``lower_secs{site}`` (Python tracing and lowering, which a persistent
  cache never saves), and captures the executable's
  ``cost_analysis()`` flops / bytes-accessed and the donated bytes it
  aliases (``alias_bytes``) into the span labels (and
  the ``xla_flops{site}`` / ``xla_bytes_accessed{site}`` gauges, which
  ``tools/trace_report.py --device`` joins with span self-time),
- diffs every *retrace* (a new signature at a site that already
  compiled one) against the site's previous signature and emits a
  zero-duration ``xla.retrace`` mark inside that compile's span, naming
  the argument that changed and how (shape / dtype / static value / structure) — the record
  rides the normal span spill into ``spans.jsonl`` and the live
  telemetry stream,
- answers subsequent calls with the cached compiled executable
  (measured: indistinguishable from jit's C++ fastpath), with the
  site's declared static positions stripped from the argument list.

The signature walk is metadata-only (``shape``/``dtype`` attributes,
never values), so the armed path adds zero device syncs and stays green
under the transfer-guard test, and what it records is a fixed function
of updates, not of solver iterations (tests/test_obs_device.py); its
share of a sweep on the chip is in PERF.md section 6 (PR 27).

Every AOT step is CONTAINED: a function the AOT API cannot lower (or an
executable whose calling convention surprises us) permanently falls the
*signature* back to the plain call — instrumentation can degrade to
uninstrumented, never break training.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY, MetricsRegistry

_ARMED = False
_REGISTRY: MetricsRegistry = REGISTRY

#: site -> _Site; module-level so repeated runs (a warm pass)
#: reuse compiled executables exactly like jit's dispatch cache would.
_SITES: dict[str, "_Site"] = {}

#: Signature cache entries use this sentinel for "AOT failed here — call
#: the plain jitted function for this signature forever".
_FALLBACK = object()


class _Site:
    __slots__ = ("name", "cache", "last_sig", "last_arg_names")

    def __init__(self, name: str):
        self.name = name
        self.cache: dict = {}  # signature -> Compiled | _FALLBACK
        self.last_sig: Optional[tuple] = None
        self.last_arg_names: Optional[Sequence[str]] = None


def arm(registry: Optional[MetricsRegistry] = None) -> None:
    """Switch the instrumented call sites live (idempotent)."""
    global _ARMED, _REGISTRY
    _REGISTRY = registry or REGISTRY
    _ARMED = True
    # the device plane's one switch: armed spans also land in any
    # jax.profiler capture, on the device trace's clock, and are kept for
    # a reader in this process (obs/trace.py, "Armed")
    trace.arm(True)


def disarm() -> None:
    global _ARMED
    _ARMED = False
    trace.arm(False)


def is_armed() -> bool:
    return _ARMED


def reset() -> None:
    """Drop every site's executable cache and signature history (test
    isolation; a long-lived process keeps its cache across runs)."""
    _SITES.clear()


def describe(x) -> tuple:
    """One argument's abstract signature: shapes/dtypes for arrays,
    recursed structure for containers and pytrees, identity for
    callables, value for hashable statics. Metadata-only — never reads
    array VALUES, so building a signature cannot sync the device."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("array", tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)))
    if isinstance(x, (list, tuple)):
        return ("seq", type(x).__name__, tuple(describe(e) for e in x))
    if isinstance(x, dict):
        return ("dict", tuple(sorted(
            (str(k), describe(v)) for k, v in x.items())))
    if x is None or isinstance(x, (bool, int, float, str)):
        return ("static", repr(x))
    if callable(x):
        # function statics hash by identity in jax's cache too: a fresh
        # closure per batch IS a retrace, and this makes it visible
        return ("fn", getattr(x, "__qualname__", type(x).__name__), id(x))
    try:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(x)
        if len(leaves) == 1 and leaves[0] is x:
            # unregistered object: tree_flatten returns it as its own
            # single leaf — recursing would never terminate
            return ("opaque", type(x).__name__, id(x))
        return ("pytree", str(treedef), tuple(describe(l) for l in leaves))
    except Exception:
        return ("opaque", type(x).__name__, id(x))


def _short(d) -> str:
    """Human-readable rendering of one argument descriptor for the
    retrace-cause record (bounded length — these land in span labels)."""
    if not isinstance(d, tuple) or not d:
        return repr(d)[:120]
    kind = d[0]
    if kind == "array":
        return f"{d[2]}{list(d[1])}" + ("w" if d[3] else "")
    if kind == "seq":
        inner = ",".join(_short(e) for e in d[2][:4])
        more = f",+{len(d[2]) - 4}" if len(d[2]) > 4 else ""
        return f"{d[1]}[{inner}{more}]"
    if kind == "static":
        return d[1][:120]
    if kind == "fn":
        return f"fn:{d[1]}@{d[2]:x}"
    if kind == "pytree":
        return f"pytree({len(d[2])} leaves)"
    return repr(d)[:120]


def _diff_field(old, new) -> str:
    """Which FACET of one argument's descriptor changed."""
    if not (isinstance(old, tuple) and isinstance(new, tuple)):
        return "value"
    if old[:1] != new[:1]:
        return "kind"
    kind = old[0]
    if kind == "array":
        if old[1] != new[1]:
            return "shape"
        if old[2] != new[2]:
            return "dtype"
        return "weak_type"
    if kind == "static":
        return "static_value"
    if kind == "fn":
        return "function_identity"
    if kind in ("seq", "dict", "pytree"):
        return "structure"
    return "value"


def _retrace_cause(old_sig, new_sig, arg_names):
    """(arg, field, old, new) for the FIRST differing argument — the
    record a shape-perturbed run needs to name its own bug. Signature
    element 0 is the function descriptor (the epilogue factory hands a
    distinct jitted function per (task, N)); elements 1.. are args."""
    for i, (o, n) in enumerate(zip(old_sig, new_sig)):
        if o != n:
            if i == 0:
                name = "<function>"
            elif arg_names and i - 1 < len(arg_names):
                name = arg_names[i - 1]
            else:
                name = f"arg{i - 1}"
            return name, _diff_field(o, n), _short(o), _short(n)
    if len(old_sig) != len(new_sig):
        return "<arity>", "arg_count", str(len(old_sig)), str(len(new_sig))
    return "<unknown>", "unknown", "", ""


def _cost_analysis(compiled) -> tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from the executable's cost analysis, or
    (None, None) where the backend doesn't report one."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None, None
    flops = cost.get("flops")
    nbytes = cost.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None)


def _alias_bytes(compiled) -> Optional[int]:
    """Bytes of donated inputs the executable really aliases to outputs
    (0 = nothing donated, or the donation was unusable), or None where the
    backend doesn't report a memory analysis."""
    try:
        return int(compiled.memory_analysis().alias_size_in_bytes)
    except Exception:
        return None


def _compile_here(site: "_Site", fn, args, static_argnums, signature):
    """Signature miss: run the compile EXPLICITLY (AOT), attribute it,
    cache the executable. Returns the call's result."""
    registry = _REGISTRY
    result = None
    # the span covers the AOT interval where it happened, so a trace shows
    # WHICH step recompiled; the labels only the finished compile knows
    # are added before it closes
    with trace.span("xla.compile", site=site.name) as compile_span:
        if site.last_sig is not None:
            arg, field, old, new = _retrace_cause(
                site.last_sig, signature, site.last_arg_names)
            registry.counter("retrace_causes").inc(site=site.name,
                                                   field=field)
            with trace.span("xla.retrace", site=site.name, arg=str(arg),
                            field=field, old=old, new=new):
                pass
        lower_secs = None
        # photonlint: allow-W201(host-side compile timing: call() bypasses this whole path when a jax trace is active)
        t0 = time.perf_counter()
        try:
            # the counter's clock runs inside the span, so both read the
            # same interval (entering an armed span makes a profiler
            # annotation, 0.3 ms on a busy host against 1.5 ms of lowering)
            with trace.span("xla.lower", site=site.name):
                # photonlint: allow-W201(host-side compile timing: call() bypasses this whole path when a jax trace is active)
                t_lower = time.perf_counter()
                lowered = fn.lower(*args)  # Python tracing + lowering
                # photonlint: allow-W201(host-side compile timing: call() bypasses this whole path when a jax trace is active)
                lower_secs = time.perf_counter() - t_lower
            compiled = lowered.compile()
        except Exception:
            # not AOT-lowerable (or convention mismatch): the plain call
            # still compiles through jit's own cache — time THAT as the
            # compile cost (first call = trace+compile+run) and pin this
            # signature to the plain path.
            result = fn(*args)
            compiled = None
            site.cache[signature] = _FALLBACK
        # photonlint: allow-W201(host-side compile timing: call() bypasses this whole path when a jax trace is active)
        secs = time.perf_counter() - t0
        labels = {"secs": round(secs, 6)}
        if compiled is not None:
            site.cache[signature] = compiled
            flops, nbytes = _cost_analysis(compiled)
            alias = _alias_bytes(compiled)
            if flops is not None:
                labels["flops"] = flops
                registry.gauge("xla_flops").set(flops, site=site.name)
            if nbytes is not None:
                labels["bytes_accessed"] = nbytes
                registry.gauge("xla_bytes_accessed").set(nbytes,
                                                         site=site.name)
            if alias is not None:
                labels["alias_bytes"] = alias
        registry.counter("compiles").inc(site=site.name)
        registry.counter("compile_secs").inc(secs, site=site.name)
        if lower_secs is not None:
            registry.counter("lower_secs").inc(lower_secs, site=site.name)
        compile_span.label(**labels)
    if compiled is not None:
        result = _call_compiled(site, fn, compiled, args, static_argnums,
                                signature)
    site.last_sig = signature
    return result


def _call_compiled(site, fn, compiled, args, static_argnums, signature):
    """Invoke a cached executable: jax's compiled calling convention
    takes the DYNAMIC arguments only, so the site's declared static
    positions are stripped. A convention surprise falls this signature
    back to the plain call permanently."""
    if static_argnums:
        statics = frozenset(static_argnums)
        dynamic = [a for i, a in enumerate(args) if i not in statics]
    else:
        dynamic = args
    try:
        return compiled(*dynamic)
    except (TypeError, ValueError):
        site.cache[signature] = _FALLBACK
        return fn(*args)


def call(site_name: str, fn, args: Sequence,
         static_argnums: Sequence[int] = (),
         arg_names: Optional[Sequence[str]] = None):
    """Call ``fn(*args)`` through the compile-attribution layer.

    ``fn`` must be a jit-wrapped callable whose static arguments (by
    POSITION in ``args``, after jax resolves ``static_argnames`` to
    positions) are listed in ``static_argnums``; ``arg_names`` (parallel
    to ``args``) names arguments in retrace-cause records. Disarmed —
    the default — this is ``fn(*args)`` plus one global check."""
    if not _ARMED:
        return fn(*args)
    import jax.core

    if not jax.core.trace_ctx.is_top_level():
        # called under jit/vmap/shard_map tracing (e.g. the vmapped
        # per-entity solver): the inner call compiles into the OUTER
        # executable — nothing to attribute here, and AOT would break
        return fn(*args)
    site = _SITES.get(site_name)
    if site is None:
        site = _SITES[site_name] = _Site(site_name)
    site.last_arg_names = arg_names
    signature = (("fn", getattr(fn, "__qualname__", type(fn).__name__),
                  id(fn)),) + tuple(describe(a) for a in args)
    cached = site.cache.get(signature)
    if cached is None:
        return _compile_here(site, fn, args, static_argnums, signature)
    site.last_sig = signature
    if cached is _FALLBACK:
        return fn(*args)
    return _call_compiled(site, fn, cached, args, static_argnums, signature)
