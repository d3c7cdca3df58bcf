"""photonlint: AST-based invariant checking for the TPU training stack.

The runtime can only spot-check this package's hard invariants where a
test happens to tread — the transfer-guard test enforces the
one-fetch-per-update contract on the paths it executes, bit-exact resume
dies silently if nondeterminism leaks into a jitted region, and the
README ``PHOTON_FAULTS`` table drifts from the actual ``fault_point()``
sites without anything noticing. These are *structural* properties of
the source (DrJAX frames the whole stack as program transformations), so
this subpackage checks them statically over the entire tree. The
analysis is whole-program: a package index resolves imports, module
constants, classes (methods, attribute types, bases) and a fixpoint
over return values, so method calls on objects built in other modules
join the dataflow and mesh axes declared anywhere ground-truth the
collectives checked everywhere.

- **W1xx sync discipline** — blocking device→host conversions
  (``float``/``int``/``bool``/``.item()``/``np.asarray``/
  ``jax.device_get``) applied to jax-array-producing expressions outside
  the instrumented fetch sites (``utils/sync_telemetry.py`` discipline).
- **W2xx jit purity / trace hazards** — impure calls (time, random,
  I/O, logging), Python branching on traced values, and host-callback
  ordering under resume (unordered ``io_callback``, impure
  ``pure_callback``) inside ``jax.jit``/``pjit``-ed functions and
  package-local functions reachable from them.
- **W3xx donation safety** — an argument passed at a ``donate_argnums``
  call site must not be read again afterwards in the same function,
  including by the next iteration of an enclosing loop.
- **W4xx fault-point drift** — ``fault_point("name")`` sites and the
  README ``PHOTON_FAULTS`` table must agree in both directions.
- **W5xx checkpoint-schema drift** — snapshot fields written at
  ``CheckpointManager.save`` sites must match the fields read back on
  the restore/resume paths.
- **W6xx collective safety** — collective axis names must come from a
  real defining site (``Mesh`` ctor / ``pmap(axis_name=...)`` /
  ``*_AXIS`` constant); no collectives under replica- or
  host-divergent control flow; ``shard_map`` spec tuples must match
  the callee's arity; ``PartitionSpec`` axes must exist.
- **W7xx retrace risk** — data-dependent shapes (``len``/``.shape``)
  flowing into jitted calls, and — given ``--trace-evidence`` —
  ``xla.retrace`` span records from a real run mapped back to the
  dispatch sites that caused them.
- **W8xx precision discipline** — low-precision reductions without an
  f32 accumulator, unguarded float64, dtype-erasing host round-trips,
  implicit mixed-dtype promotion in loss/grad paths.
- **W9xx thread safety** — inconsistently guarded shared state,
  non-async-signal-safe handlers, unjoined threads, lock-order
  inversion.
- **WAxx wire-protocol drift** — serve-plane string contracts: NDJSON
  ``kind``s sent vs dispatched, typed-error names raised/rendered vs
  the ``typed_error`` parse table and the transport-classification
  set, writer field sets vs kind-pinned reader accesses.
- **WBxx telemetry-taxonomy drift** — metric/span names emitted vs the
  README taxonomy tables vs every consumer (``photon_status``,
  trace tools, chaos assertions — loaded as auxiliary modules), plus
  label-key drift between emit sites sharing a name.

Entry points: :func:`photon_ml_tpu.analysis.runner.lint` (library) and
``tools/photonlint.py`` (CLI). Per-line suppressions use
``# photonlint: allow-<rule>(reason)`` and a committed baseline file
grandfathers known findings (see README "Static analysis"). Runs can
be incremental: ``cache_dir=`` / ``--cache-dir`` keys per-file
artifacts and a whole-program findings replay on content hashes (see
:mod:`photon_ml_tpu.analysis.cache`).
"""

from photon_ml_tpu.analysis.core import Finding, LintReport  # noqa: F401
from photon_ml_tpu.analysis.runner import lint  # noqa: F401
