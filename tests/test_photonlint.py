"""photonlint tier-1 gate + rule-family unit tests.

Three layers:

1. Fixture snippets: every rule family has a positive case (fires), a
   negative case (stays quiet), and a suppressed case (fires but a
   ``# photonlint: allow-...`` directive absorbs it), plus baseline
   round-trip and malformed-directive coverage.
2. The package gate: ``photon_ml_tpu/`` must produce ZERO non-baselined
   findings against the committed baseline (failure prints the findings
   as a readable diff, not a bare assert).
3. Canaries: a copy of the real package is seeded with one known
   violation per family and the lint run MUST go red for each — proving
   the gate cannot silently rot.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from photon_ml_tpu.analysis import core, runner

REPO_ROOT = Path(__file__).resolve().parents[1]
BASELINE = REPO_ROOT / "tools" / "photonlint_baseline.json"
README = REPO_ROOT / "README.md"


def run_fixture(tmp_path, files, readme=None, families=None,
                baseline=None, trace_dir=None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    for name, src in files.items():
        path = pkg / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    readme_path = None
    if readme is not None:
        readme_path = tmp_path / "README.md"
        readme_path.write_text(readme)
    return runner.lint(tmp_path, paths=["pkg"], readme=readme_path,
                       baseline=baseline, families=families,
                       trace_dir=trace_dir)


def rules_of(report):
    return sorted({f.rule for f in report.new})


# -- W1xx sync discipline --------------------------------------------------

W1_POSITIVE = """
import jax
import jax.numpy as jnp
import numpy as np

def objective():
    x = jnp.zeros((4,))
    loss = float(jnp.sum(x))        # W101
    flag = bool(jnp.all(x > 0))     # W101
    one = jnp.max(x).item()         # W102
    host = np.asarray(x)            # W103
    rest = jax.device_get(x)        # W104 (no record_host_fetch)
    return loss, flag, one, host, rest
"""

W1_NEGATIVE = """
import jax
import jax.numpy as jnp
import numpy as np
from photon_ml_tpu.utils.sync_telemetry import record_host_fetch

def objective():
    x = jnp.zeros((4,))
    fetched = jax.device_get((jnp.sum(x), jnp.all(x > 0)))
    record_host_fetch()
    loss, flag = fetched
    host = np.asarray([1.0, 2.0])   # numpy input: free
    return float(loss), bool(flag), host
"""

W1_SUPPRESSED = """
import jax.numpy as jnp

def objective():
    x = jnp.zeros((4,))
    # photonlint: allow-W101(fixture: intentional scalar sync)
    return float(jnp.sum(x))
"""


def test_w1_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W1_POSITIVE},
                         families={"W1"})
    assert rules_of(report) == ["W101", "W102", "W103", "W104"]
    assert sum(f.rule == "W101" for f in report.new) == 2


def test_w1_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W1_NEGATIVE},
                         families={"W1"})
    assert report.new == []


def test_w1_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W1_SUPPRESSED},
                         families={"W1"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W101"]


# -- W105 pipeline-depth discipline ----------------------------------------

W105_POSITIVE = """
def sweep(dispatch_update, resolve_update, blocks):
    p0 = dispatch_update(blocks[0])
    p1 = dispatch_update(blocks[1])
    p2 = dispatch_update(blocks[2])   # W105: p0 now two dispatches old
    resolve_update(p0)
    resolve_update(p1)
    resolve_update(p2)
"""

W105_LOOP_POSITIVE = """
def sweep(dispatch_update, resolve_update, blocks):
    older = None
    newer = None
    for b in blocks:
        cur = dispatch_update(b)      # W105: 'older' survives 2 dispatches
        if older is not None:
            resolve_update(older)
        older = newer
        newer = cur
"""

W105_NEGATIVE = """
def sweep(dispatch_update, resolve_update, fetch_update, blocks):
    pending = None
    for b in blocks:
        cur = dispatch_update(b)      # depth 1: pending is one old, fine
        if pending is not None:
            resolve_update(pending)
        pending = cur
    if pending is not None:
        resolve_update(pending)

def ladder(dispatch_update, fetch_update, b):
    p = dispatch_update(b)
    objective, loss = fetch_update(p)
    return objective, loss
"""

W105_SUPPRESSED = """
def sweep(dispatch_update, resolve_update, blocks):
    p0 = dispatch_update(blocks[0])
    p1 = dispatch_update(blocks[1])
    # photonlint: allow-W105(fixture: bounded two-deep drain follows)
    p2 = dispatch_update(blocks[2])
    for p in (p0, p1, p2):
        resolve_update(p)
"""


def test_w105_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W105_POSITIVE},
                         families={"W1"})
    assert rules_of(report) == ["W105"]
    assert "p0" in report.new[0].message


def test_w105_loop_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W105_LOOP_POSITIVE},
                         families={"W1"})
    assert "W105" in rules_of(report)


def test_w105_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W105_NEGATIVE},
                         families={"W1"})
    assert report.new == []


def test_w105_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W105_SUPPRESSED},
                         families={"W1"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W105"]


# -- W2xx jit purity -------------------------------------------------------

W2_POSITIVE = """
import time
import jax
import jax.numpy as jnp

@jax.jit
def kernel(x):
    stamp = time.time()             # W201
    if x > 0:                       # W202 (x is a tracer)
        return x * stamp
    return -x

def helper(y):
    print("tracing", y)             # W201 via call graph
    return y * 2.0

@jax.jit
def outer(y):
    return helper(y)
"""

W2_NEGATIVE = """
from functools import partial
import jax
import jax.numpy as jnp

@partial(jax.jit, static_argnames=("flip",))
def kernel(x, flip):
    if flip:                        # static arg: fine
        return -x
    if x is None:                   # identity check: fine
        return jnp.zeros(())
    return jnp.where(x > 0, x, -x)  # data-dependence the jit way

def helper(y):
    print("not traced")             # not reachable from any jit
    return y
"""

W2_SUPPRESSED = """
import time
import jax

@jax.jit
def kernel(x):
    # photonlint: allow-W201(fixture: trace-time stamp is intended)
    return x * time.time()
"""


def test_w2_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W2_POSITIVE},
                         families={"W2"})
    assert rules_of(report) == ["W201", "W202"]
    w201 = [f for f in report.new if f.rule == "W201"]
    assert any("reachable from" in f.message for f in w201), \
        "call-graph reachability must attribute helper() to its jit root"


def test_w2_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W2_NEGATIVE},
                         families={"W2"})
    assert report.new == []


def test_w2_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W2_SUPPRESSED},
                         families={"W2"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W201"]


# -- W3xx donation safety --------------------------------------------------

W3_POSITIVE = """
import jax
import jax.numpy as jnp

def step(x):
    return x + 1

_step_donating = jax.jit(step, donate_argnums=(0,))

def run(buf):
    out = _step_donating(buf)
    return out + buf                # W301: buf was donated
"""

W3_NEGATIVE = """
import jax
import jax.numpy as jnp

def step(x):
    return x + 1

_step_donating = jax.jit(step, donate_argnums=(0,))

def run(buf):
    out = _step_donating(buf)       # last read of buf: fine
    buf = jnp.zeros_like(out)       # rebind kills the hazard
    return out + buf
"""

W3_SUPPRESSED = """
import jax

def run(buf):
    fn = jax.jit(lambda b: b + 1, donate_argnums=(0,))
    # photonlint: allow-W301(fixture: CPU backend never aliases)
    out = fn(buf)
    return out + buf
"""


def test_w3_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W3_POSITIVE},
                         families={"W3"})
    assert rules_of(report) == ["W301"]


def test_w3_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W3_NEGATIVE},
                         families={"W3"})
    assert report.new == []


def test_w3_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W3_SUPPRESSED},
                         families={"W3"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W301"]


# -- W4xx fault-point drift ------------------------------------------------

FAULT_README = """# fixture
| point | fires | tag |
|---|---|---|
| `cd.update` | after each update | sweep.coord |
| `ghost.point` | documented but gone | — |
"""

W4_POSITIVE = """
from photon_ml_tpu.utils.faults import fault_point

def body():
    fault_point("cd.update", tag="1.1")
    fault_point("cd.unlisted")      # W401: not in the table
    name = "dyn"
    fault_point(name)               # W403: not a literal
"""

W4_NEGATIVE = """
from photon_ml_tpu.utils.faults import fault_point

def body():
    fault_point("cd.update", tag="1.1")
"""

W4_SUPPRESSED = """
from photon_ml_tpu.utils.faults import fault_point

def body():
    fault_point("cd.update", tag="1.1")
    # photonlint: allow-W401(fixture: experimental point, not yet documented)
    fault_point("cd.unlisted")
"""


def test_w4_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W4_POSITIVE},
                         readme=FAULT_README, families={"W4"})
    assert rules_of(report) == ["W401", "W402", "W403"]
    w402 = [f for f in report.new if f.rule == "W402"]
    assert "ghost.point" in w402[0].message
    assert w402[0].path == "README.md"


def test_w4_negative(tmp_path):
    readme = FAULT_README.replace(
        "| `ghost.point` | documented but gone | — |\n", "")
    report = run_fixture(tmp_path, {"mod.py": W4_NEGATIVE},
                         readme=readme, families={"W4"})
    assert report.new == []


def test_w4_suppressed(tmp_path):
    readme = FAULT_README.replace(
        "| `ghost.point` | documented but gone | — |\n", "")
    report = run_fixture(tmp_path, {"mod.py": W4_SUPPRESSED},
                         readme=readme, families={"W4"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W401"]


# -- W5xx checkpoint-schema drift ------------------------------------------

W5_POSITIVE = """
def save(ckpt_mgr, sweep, states):
    state = {"sweep": sweep, "states": states, "orphan": 1}  # W502
    ckpt_mgr.save(sweep, state)

def resume(ckpt_mgr):
    snap = ckpt_mgr.restore()
    return snap["sweep"], snap["states"], snap.get("phantom")  # W501
"""

W5_NEGATIVE = """
def save(ckpt_mgr, sweep, states):
    ckpt_mgr.save(sweep, {"sweep": sweep, "states": states})

def resume(ckpt_mgr):
    snap = ckpt_mgr.restore()
    return snap["sweep"], snap.get("states")
"""

W5_SUPPRESSED = """
def save(ckpt_mgr, sweep):
    ckpt_mgr.save(sweep, {"sweep": sweep})

def resume(ckpt_mgr):
    snap = ckpt_mgr.restore()
    # photonlint: allow-W501(fixture: key written by an older release)
    return snap["legacy_field"], snap["sweep"]
"""


def test_w5_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W5_POSITIVE},
                         families={"W5"})
    assert rules_of(report) == ["W501", "W502"]
    assert any("phantom" in f.message for f in report.new)
    assert any("orphan" in f.message for f in report.new)


def test_w5_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W5_NEGATIVE},
                         families={"W5"})
    assert report.new == []


def test_w5_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W5_SUPPRESSED},
                         families={"W5"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W501"]


W5_WRAPPER = """
def _checkpoint_save_contained(manager, step, snapshot):
    manager.save(step, snapshot)

def save(mgr, sweep, states):
    _checkpoint_save_contained(mgr, sweep,
                               {"sweep": sweep, "states": states})
    # name-alike 2-arg helper: NOT a save site — its dict must not
    # widen the written-key union (it would be a false W502)
    save_checkpoint_report(mgr, {"path": "out", "elapsed": 1.0})

def save_checkpoint_report(mgr, info):
    pass

def resume(ckpt_mgr):
    snap = ckpt_mgr.restore()
    return snap["sweep"], snap.get("states")
"""


def test_w5_save_wrapper_counts_as_writer(tmp_path):
    """A dict passed to a checkpoint-save containment wrapper
    (`_checkpoint_save_contained(mgr, step, {...})`) is a save site:
    hoisting `.save` into a helper must not blind the schema check
    (it would W501 every key the wrapper writes). A 2-arg helper whose
    name merely matches is NOT one — its dict stays out of the union."""
    report = run_fixture(tmp_path, {"mod.py": W5_WRAPPER},
                         families={"W5"})
    assert report.new == []


def test_w3_self_rebind_is_clean(tmp_path):
    """`x = donating(x)` — THE idiomatic donation pattern — must not
    fire: the name is rebound to the result the moment the call
    returns."""
    src = """
import jax

def step(x):
    return x + 1

_step = jax.jit(step, donate_argnums=(0,))

def run(x, n):
    for _ in range(n):
        x = _step(x)
    return x
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W3"})
    assert report.new == []


def test_w3_same_line_read_fires(tmp_path):
    """A read of the donated buffer on the call's own line is exactly
    the deleted-buffer bug — line granularity must not hide it."""
    src = """
import jax

def step(x):
    return x + 1

_step = jax.jit(step, donate_argnums=(0,))

def run(buf):
    return _step(buf) + buf
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W3"})
    assert rules_of(report) == ["W301"]


# -- W203 host-callback ordering under resume ------------------------------

W203_POSITIVE = """
import time
import jax
import jax.numpy as jnp
from jax.experimental import io_callback

def note(x):
    return None

@jax.jit
def kernel(x):
    io_callback(note, None, x)                       # W203: unordered
    t = jax.pure_callback(
        time.time, jax.ShapeDtypeStruct((), jnp.float32))  # W203: impure
    return x * t
"""

W203_NEGATIVE = """
import jax
import jax.numpy as jnp
from jax.experimental import io_callback

def note(x):
    return None

def pure_sq(x):
    return x * x

@jax.jit
def kernel(x):
    io_callback(note, None, x, ordered=True)         # ordered: fine
    y = jax.pure_callback(
        pure_sq, jax.ShapeDtypeStruct((), jnp.float32), x)
    return x + y

def host_only(x):
    io_callback(note, None, x)   # not jit-reachable: out of scope
    return x
"""

W203_SUPPRESSED = """
import jax
from jax.experimental import io_callback

def note(x):
    return None

@jax.jit
def kernel(x):
    # photonlint: allow-W203(fixture: effect is idempotent, order-free)
    io_callback(note, None, x)
    return x
"""


def test_w203_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W203_POSITIVE},
                         families={"W2"})
    w203 = [f for f in report.new if f.rule == "W203"]
    assert len(w203) == 2
    assert any("ordered=True" in f.message for f in w203)
    assert any("time.time" in f.message for f in w203)


def test_w203_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W203_NEGATIVE},
                         families={"W2"})
    assert report.new == []


def test_w203_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W203_SUPPRESSED},
                         families={"W2"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W203"]


# -- W301 loop-carried donation reads --------------------------------------

def test_w301_loop_carried_positive(tmp_path):
    """A buffer donated inside a loop without a rebind is read (deleted)
    again by the NEXT iteration — the carried-over lint debt."""
    src = """
import jax

def step(x):
    return x + 1

_step = jax.jit(step, donate_argnums=(0,))

def run(buf, n):
    acc = 0.0
    for _ in range(n):
        acc = acc + _step(buf)      # W301: buf never rebound in loop
    return acc
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W3"})
    assert rules_of(report) == ["W301"]
    assert "next iteration" in report.new[0].message


def test_w301_loop_carried_negative_fresh_buffer(tmp_path):
    """A buffer created fresh each iteration before the donating call is
    a new allocation every time — no loop-carried hazard."""
    src = """
import jax
import jax.numpy as jnp

def step(x):
    return x + 1

_step = jax.jit(step, donate_argnums=(0,))

def run(n):
    acc = 0.0
    for i in range(n):
        buf = jnp.full((4,), float(i))
        acc = acc + _step(buf)
    return acc
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W3"})
    assert report.new == []


# -- cross-module receiver-type inference ----------------------------------

RECEIVER_CLASS_MOD = """
import jax.numpy as jnp

class Scorer:
    def __init__(self, scale):
        self.scale = scale

    def score(self, x):
        return jnp.sum(x) * self.scale

    def label(self):
        return "scorer"

class Holder:
    def __init__(self):
        self.scorer = Scorer(1.0)
"""

RECEIVER_USE_MOD = """
from pkg.mod_a import Scorer, Holder

def evaluate(x):
    s = Scorer(2.0)
    return float(s.score(x))        # W101: method resolves cross-module

def evaluate_chain(x):
    h = Holder()
    return float(h.scorer.score(x))  # W101: through the attribute index

def describe():
    s = Scorer(2.0)
    return float(len(s.label()))    # str-returning method: clean
"""


def test_cross_module_receiver_inference(tmp_path):
    report = run_fixture(
        tmp_path,
        {"mod_a.py": RECEIVER_CLASS_MOD, "mod_b.py": RECEIVER_USE_MOD},
        families={"W1"})
    w101 = [f for f in report.new if f.rule == "W101"]
    assert len(w101) == 2, [f.format() for f in report.new]
    assert all(f.path == "pkg/mod_b.py" for f in w101)
    assert {f.line for f in w101} == {6, 10}


def test_receiver_inference_host_annotation_trusted(tmp_path):
    """A method annotated ``-> float`` is a deliberate host accessor:
    its CALLERS must not be re-flagged for consuming the result."""
    class_mod = """
import jax.numpy as jnp

class Penalty:
    def value_device(self, x):
        return jnp.sum(x * x)

    def value(self, x) -> float:
        v = self.value_device(x)
        # photonlint: allow-W101(the designated host accessor syncs here)
        return v if isinstance(v, float) else float(v)
"""
    use_mod = """
from pkg.mod_a import Penalty

def objective(x):
    p = Penalty()
    return 2.0 * float(p.value(x))   # already host: clean
"""
    report = run_fixture(
        tmp_path, {"mod_a.py": class_mod, "mod_b.py": use_mod},
        families={"W1"})
    assert report.new == [], [f.format() for f in report.new]


# -- W6xx collective safety ------------------------------------------------

MESH_MOD = """
import jax
from jax.sharding import Mesh

DATA_AXIS = "data"
ENTITY_AXIS = "entity"

def make_mesh(devs):
    return Mesh(devs, (DATA_AXIS, ENTITY_AXIS))
"""

W601_POSITIVE = """
from jax import lax

def exchange(x):
    return lax.psum(x, "entty")     # W601: typo'd axis
"""

W601_NEGATIVE = """
import jax
from jax import lax
from pkg.mesh import ENTITY_AXIS

def score(x, mesh):
    def impl(v):
        return lax.psum(v, ENTITY_AXIS)   # correct psum inside shard_map
    fn = jax.shard_map(impl, mesh=mesh, in_specs=(None,),
                       out_specs=None)
    return fn(x)

def gather(x, axis_name):
    return lax.all_gather(x, axis_name)   # unresolvable param: skipped
"""

W601_SUPPRESSED = """
from jax import lax

def exchange(x):
    # photonlint: allow-W601(fixture: axis is created by the test harness)
    return lax.psum(x, "harness_axis")
"""


def test_w601_positive_names_offender_and_candidates(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W601_POSITIVE},
        families={"W6"})
    assert rules_of(report) == ["W601"]
    msg = report.new[0].message
    assert "'entty'" in msg, "must name the offending axis"
    assert "'data'" in msg and "'entity'" in msg, \
        "must name the candidate axes"


def test_w601_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W601_NEGATIVE},
        families={"W6"})
    assert report.new == [], [f.format() for f in report.new]


def test_w601_suppressed(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W601_SUPPRESSED},
        families={"W6"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W601"]


W602_POSITIVE = """
import jax
import jax.numpy as jnp
from jax import lax

def exchange(x):
    if jax.process_index() == 0:
        return lax.psum(x, "data")  # W602: only host 0 reaches it
    return x

def accept_gate(x):
    flag = jnp.sum(x)
    while flag > 0:                 # traced predicate
        x = lax.pmean(x, "data")    # W602: replicas may disagree
        flag = jnp.sum(x)
    return x
"""

W602_NEGATIVE = """
from jax import lax

def exchange(x, enabled):
    if enabled:                     # host-uniform config flag: fine
        return lax.psum(x, "data")
    return x

def always(x):
    return lax.pmean(x, "data")     # unconditional: fine
"""


def test_w602_positive(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W602_POSITIVE},
        families={"W6"})
    w602 = [f for f in report.new if f.rule == "W602"]
    assert len(w602) == 2, [f.format() for f in report.new]
    assert any("process_index" in f.message for f in w602)
    assert any("traced per-replica value" in f.message for f in w602)


def test_w602_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W602_NEGATIVE},
        families={"W6"})
    assert report.new == []


W603_POSITIVE = """
import jax

def run(x, mesh):
    def impl(a, b):
        return a + b
    fn = jax.shard_map(impl, mesh=mesh, in_specs=(None,),
                       out_specs=None)      # W603: 1 spec, 2 params
    return fn(x)

def run2(x, mesh):
    def impl2(a):
        return a, a
    fn = jax.shard_map(impl2, mesh=mesh, in_specs=(None,),
                       out_specs=(None, None, None))  # W603: 3 vs 2
    return fn(x)
"""

W603_NEGATIVE = """
import jax

def run(x, y, mesh):
    def impl(a, b):
        return a + b, a - b
    fn = jax.shard_map(impl, mesh=mesh, in_specs=(None, None),
                       out_specs=(None, None))
    return fn(x, y)

def run_conditional(x, mesh, fast):
    # a callee name that is ALSO assigned is ambiguous: skipped
    if fast:
        local = _make_impl()
    else:
        def local(a):
            return a
    fn = jax.shard_map(local, mesh=mesh, in_specs=(None, None),
                       out_specs=None)
    return fn(x)

def _make_impl():
    def impl(a, b):
        return a
    return impl
"""


def test_w603_positive(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W603_POSITIVE},
        families={"W6"})
    w603 = [f for f in report.new if f.rule == "W603"]
    assert len(w603) == 2, [f.format() for f in report.new]
    assert any("takes 2 positional" in f.message for f in w603)
    assert any("out_specs" in f.message for f in w603)


def test_w603_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W603_NEGATIVE},
        families={"W6"})
    assert report.new == [], [f.format() for f in report.new]


W604_POSITIVE = """
from jax.sharding import PartitionSpec as P

def specs():
    return P("bogus_axis")          # W604
"""

W604_NEGATIVE = """
from jax.sharding import PartitionSpec as P
from pkg.mesh import DATA_AXIS

def specs():
    return P(DATA_AXIS), P("entity"), P()
"""


def test_w604_positive(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W604_POSITIVE},
        families={"W6"})
    assert rules_of(report) == ["W604"]
    assert "'bogus_axis'" in report.new[0].message


def test_w604_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W604_NEGATIVE},
        families={"W6"})
    assert report.new == []


def test_w601_seeded_axis_typo_in_random_effect(tmp_path_factory):
    """The acceptance scenario: a deliberate axis-name typo seeded into
    a scratch copy of ``game/random_effect.py``'s score-exchange psum
    must produce a W601 naming both the offender and the candidates."""
    root = tmp_path_factory.mktemp("axis_typo")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    target = root / "photon_ml_tpu" / "game" / "random_effect.py"
    src = target.read_text()
    # PR 18 routed the score exchange through the quantized qpsum
    # wrapper; W601 treats it as a collective, so the typo protection
    # must survive the wrapper swap.
    needle = "qpsum(flat[:num_samples], ENTITY_AXIS,"
    assert needle in src, "score-exchange psum moved; update this test"
    target.write_text(src.replace(
        needle, 'qpsum(flat[:num_samples], "entty",'))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         families={"W6"})
    w601 = [f for f in report.new if f.rule == "W601"]
    assert len(w601) == 1, [f.format() for f in report.new]
    f = w601[0]
    assert f.path == "photon_ml_tpu/game/random_effect.py"
    assert "'entty'" in f.message
    assert "'data'" in f.message and "'entity'" in f.message


# -- W7xx retrace risk -----------------------------------------------------

W701_POSITIVE = """
import jax
import jax.numpy as jnp

@jax.jit
def kernel(v):
    return v * 2

def run(xs):
    n = len(xs)
    return kernel(jnp.zeros(n))     # W701: shape follows len(xs)

def run_shape(batch):
    rows = batch.shape[0]
    return kernel(jnp.ones((rows, 4)))   # W701: shape follows .shape
"""

W701_NEGATIVE = """
import jax
import jax.numpy as jnp

@jax.jit
def kernel(v):
    return v * 2

def pad_to_bucket(n):
    return max(8, 1 << (int(n) - 1).bit_length())

def run(xs):
    n = pad_to_bucket(len(xs))      # bucketed: shape-stable
    return kernel(jnp.zeros(n))

def run_const(xs):
    return kernel(jnp.zeros(128))   # static shape: fine
"""

W701_SUPPRESSED = """
import jax
import jax.numpy as jnp

@jax.jit
def kernel(v):
    return v * 2

def run(xs):
    n = len(xs)
    # photonlint: allow-W701(fixture: xs has one size in this pipeline)
    return kernel(jnp.zeros(n))
"""


def test_w701_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W701_POSITIVE},
                         families={"W7"})
    w701 = [f for f in report.new if f.rule == "W701"]
    assert len(w701) == 2, [f.format() for f in report.new]
    assert any("len(...)" in f.message for f in w701)
    assert any(".shape" in f.message for f in w701)


def test_w701_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W701_NEGATIVE},
                         families={"W7"})
    assert report.new == [], [f.format() for f in report.new]


def test_w701_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W701_SUPPRESSED},
                         families={"W7"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W701"]


W702_SITE_MOD = """
from photon_ml_tpu.obs import compile as obs_compile

def dispatch(fn, batch):
    return obs_compile.call("fix.site", fn, (batch,),
                            arg_names=("batch",))
"""


def _write_trace(tmp_path, records):
    trace = tmp_path / "trace"
    trace.mkdir()
    lines = [json.dumps(r) for r in records]
    (trace / "spans.jsonl").write_text("\n".join(lines) + "\n")
    return trace


def test_w702_with_trace_evidence(tmp_path):
    trace = _write_trace(tmp_path, [
        {"name": "span.other", "labels": {}},
        {"name": "xla.retrace",
         "labels": {"site": "fix.site", "arg": "batch",
                    "field": "shape", "old": "(8, 4)",
                    "new": "(9, 4)"}},
        {"name": "xla.retrace",   # same site+arg: deduplicated
         "labels": {"site": "fix.site", "arg": "batch",
                    "field": "shape", "old": "(9, 4)",
                    "new": "(10, 4)"}},
        {"name": "xla.retrace",   # site with no source location: skipped
         "labels": {"site": "unknown.site", "arg": "x"}},
    ])
    report = run_fixture(tmp_path, {"mod.py": W702_SITE_MOD},
                         families={"W7"}, trace_dir=trace)
    w702 = [f for f in report.new if f.rule == "W702"]
    assert len(w702) == 1, [f.format() for f in report.new]
    f = w702[0]
    assert f.path == "pkg/mod.py"
    assert "'fix.site'" in f.message
    assert "(8, 4)" in f.message and "(9, 4)" in f.message


def test_w702_without_trace_evidence_is_silent(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W702_SITE_MOD},
                         families={"W7"})
    assert report.new == []


def test_w702_garbage_trace_lines_are_skipped(tmp_path):
    trace = tmp_path / "trace"
    trace.mkdir()
    (trace / "spans.jsonl").write_text(
        "not json at all\n{\"name\": \"xla.retrace\"\n\n")
    report = run_fixture(tmp_path, {"mod.py": W702_SITE_MOD},
                         families={"W7"}, trace_dir=trace)
    assert report.new == []


# -- W002 stale suppressions + baseline pruning ----------------------------

def test_w002_stale_suppression_fires(tmp_path):
    src = """
import jax.numpy as jnp

def f(x):
    # photonlint: allow-W102(stale: the .item() call was removed)
    return x + 1
"""
    report = run_fixture(tmp_path, {"mod.py": src})
    w002 = [f for f in report.new if f.rule == "W002"]
    assert len(w002) == 1
    assert "allow-W102" in w002[0].message


def test_w002_used_suppression_is_clean(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W1_SUPPRESSED})
    assert [f.rule for f in report.suppressed] == ["W101"]
    assert not [f for f in report.new if f.rule == "W002"]


def test_w002_skipped_on_family_subset_runs(tmp_path):
    """On a partial run an off-family directive merely LOOKS unused —
    W002 must only judge directives when every family has spoken."""
    report = run_fixture(tmp_path, {"mod.py": W1_SUPPRESSED},
                         families={"W2"})
    assert report.new == []


def test_write_baseline_prunes_stale_entries(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(W1_POSITIVE)
    baseline = tmp_path / "baseline.json"
    n = runner.write_baseline(tmp_path, baseline, paths=["pkg"],
                              families={"W1"})
    assert n > 0

    (pkg / "mod.py").write_text(W1_NEGATIVE)  # everything fixed
    n = runner.write_baseline(tmp_path, baseline, paths=["pkg"],
                              families={"W1"})
    assert n == 0
    assert core.load_baseline(baseline) == [], \
        "stale entries must not be carried forever"


def test_cli_write_baseline_reports_pruned(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(W1_POSITIVE)
    baseline = tmp_path / "baseline.json"
    cli = [sys.executable, str(REPO_ROOT / "tools" / "photonlint.py"),
           "pkg", "--root", str(tmp_path), "--baseline", str(baseline),
           "--rules", "W1", "--write-baseline"]
    proc = subprocess.run(cli, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    (pkg / "mod.py").write_text(W1_NEGATIVE)
    proc = subprocess.run(cli, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned" in proc.stdout


# -- W4xx reconcile pins for the PR 11-12 fault points ---------------------

@pytest.mark.parametrize("point,site_file", [
    ("obs.otlp", "photon_ml_tpu/obs/otlp.py"),
    ("re.shard_dispatch", "photon_ml_tpu/game/random_effect.py"),
])
def test_fault_point_round_trip_pinned(tmp_path_factory, point,
                                       site_file):
    """The PR 11-12 fault points round-trip between README table and
    call sites: the real tree is clean (the package gate), and renaming
    the README row makes BOTH directions fire — W401 at the real call
    site and W402 for the now-phantom row."""
    readme_text = README.read_text()
    assert f"| `{point}` |" in readme_text, \
        f"README PHOTON_FAULTS table lost its {point} row"

    root = tmp_path_factory.mktemp(f"faultpin_{point.replace('.', '_')}")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "README.md").write_text(readme_text.replace(
        f"| `{point}` |", f"| `{point}.phantom` |"))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE)
    w401 = [f for f in report.new if f.rule == "W401"
            and f'"{point}"' in f.message]
    assert w401, f"no W401 for the undocumented {point} call site"
    assert all(f.path == site_file for f in w401)
    w402 = [f for f in report.new if f.rule == "W402"
            and f"{point}.phantom" in f.message]
    assert w402, f"no W402 for the phantom {point} README row"


# -- SARIF output ----------------------------------------------------------

def test_sarif_fixture_shape(tmp_path):
    from photon_ml_tpu.analysis.sarif import to_sarif

    report = run_fixture(
        tmp_path, {"mesh.py": MESH_MOD, "mod.py": W601_POSITIVE},
        families={"W6"})
    doc = to_sarif(report)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "photonlint"
    rules = run["tool"]["driver"]["rules"]
    assert {r["id"] for r in rules} == set(core.RULES)
    # per-rule metadata: a shortDescription and a helpUri into the
    # README rule-catalog anchor, for SARIF viewers
    for r in rules:
        assert r["shortDescription"]["text"] == core.RULES[r["id"]]
        assert r["helpUri"].endswith("README.md#rule-catalog")
    results = run["results"]
    assert len(results) == 1
    assert results[0]["ruleId"] == "W601"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/mod.py"
    assert loc["region"]["startLine"] == report.new[0].line


def test_cli_sarif_exit_zero():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "photonlint.py"),
         "photon_ml_tpu", "--sarif"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == "2.1.0"
    assert payload["runs"][0]["results"] == []


# -- suppression grammar / W001 --------------------------------------------

def test_malformed_suppression_is_w001(tmp_path):
    src = """
import jax.numpy as jnp

def f():
    x = jnp.zeros(())
    # photonlint: allow-W101()
    return float(x)
"""
    report = run_fixture(tmp_path, {"mod.py": src})
    rules = rules_of(report)
    assert "W001" in rules, "empty reason must not silently suppress"
    assert "W101" in rules, "the malformed directive must not suppress"


def test_standalone_suppression_skips_blank_and_comment_lines(tmp_path):
    src = """
import jax.numpy as jnp

def f():
    x = jnp.zeros(())
    # photonlint: allow-W101(fixture: guarded through intervening comment)
    # an explanatory comment between directive and statement

    return float(x)
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W1"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W101"]


def test_family_wildcard_suppression(tmp_path):
    src = """
import jax.numpy as jnp

def f():
    x = jnp.zeros(())
    # photonlint: allow-W1xx(fixture: whole-family waiver)
    return float(x)
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"W1"})
    assert report.new == []
    assert len(report.suppressed) == 1


# -- baseline workflow -----------------------------------------------------

def test_baseline_round_trip(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(W1_POSITIVE)
    baseline = tmp_path / "baseline.json"

    first = runner.lint(tmp_path, paths=["pkg"], families={"W1"})
    assert len(first.new) == 5

    n = runner.write_baseline(tmp_path, baseline, paths=["pkg"],
                              families={"W1"})
    assert n == len({f.baseline_key for f in first.new})

    second = runner.lint(tmp_path, paths=["pkg"], baseline=baseline,
                         families={"W1"})
    assert second.new == [], "baselined findings must not re-fire"
    assert len(second.baselined) == 5

    # a NEW violation on top of the baseline still goes red
    (pkg / "mod.py").write_text(
        W1_POSITIVE + "\n\ndef extra():\n"
        "    import jax.numpy as jnp\n"
        "    return int(jnp.ones(()))\n")
    third = runner.lint(tmp_path, paths=["pkg"], baseline=baseline,
                        families={"W1"})
    assert len(third.new) == 1
    assert third.new[0].rule == "W101"  # int() on jax value

    # fixing everything leaves stale entries, reported not fatal
    (pkg / "mod.py").write_text(W1_NEGATIVE)
    fourth = runner.lint(tmp_path, paths=["pkg"], baseline=baseline,
                         families={"W1"})
    assert fourth.new == []
    assert fourth.stale_baseline, "fixed findings should show as stale"


# -- the package gate ------------------------------------------------------

def _format_failure(report):
    lines = ["photonlint found NEW violations (fix them, suppress with "
             "# photonlint: allow-<rule>(reason), or — for a "
             "deliberate grandfather — run "
             "`python tools/photonlint.py --write-baseline`):", ""]
    lines += [f"  {f.format()}" for f in report.new]
    return "\n".join(lines)


def test_package_has_no_new_findings(tmp_path):
    """The tier-1 gate — run THROUGH the incremental cache: cold run
    populates, the replay must be at least 2x faster with identical
    findings, and a changed-input rerun (different family subset →
    different program key) must reuse >=90% of the per-file artifacts."""
    import time as time_mod

    cache_dir = tmp_path / "photonlint_cache"
    t0 = time_mod.perf_counter()
    report = runner.lint(REPO_ROOT, paths=["photon_ml_tpu"],
                         readme=README, baseline=BASELINE,
                         cache_dir=cache_dir)
    cold_secs = time_mod.perf_counter() - t0
    assert report.ok, _format_failure(report)
    assert report.cache_stats["file_misses"] > 0

    t0 = time_mod.perf_counter()
    again = runner.lint(REPO_ROOT, paths=["photon_ml_tpu"],
                        readme=README, baseline=BASELINE,
                        cache_dir=cache_dir)
    warm_secs = time_mod.perf_counter() - t0
    assert again.cache_stats["program_hit"]
    assert again.format_json() == report.format_json(), \
        "cached replay must be byte-identical to the cold run"
    assert warm_secs < cold_secs / 2, \
        f"cached rerun not faster: {warm_secs:.2f}s vs {cold_secs:.2f}s"

    subset = runner.lint(REPO_ROOT, paths=["photon_ml_tpu"],
                         readme=README, baseline=BASELINE,
                         families={"WA", "WB"}, cache_dir=cache_dir)
    cs = subset.cache_stats
    hit_rate = cs["file_hits"] / (cs["file_hits"] + cs["file_misses"])
    assert hit_rate >= 0.9, f"file-level hit rate {hit_rate:.0%}"


def test_cli_json_exit_zero():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "photonlint.py"),
         "photon_ml_tpu", "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["new"] == []
    assert payload["files_checked"] > 50


# -- canaries: every family must still fire on a seeded violation ----------

CANARIES = {
    "W101": (
        "\n\ndef _photonlint_canary_sync():\n"
        "    return float(jnp.sum(jnp.zeros((3,))))\n"),
    "W105": (
        "\n\ndef _photonlint_canary_pipeline(dispatch_update, "
        "resolve_update):\n"
        "    p0 = dispatch_update(0)\n"
        "    p1 = dispatch_update(1)\n"
        "    p2 = dispatch_update(2)\n"
        "    for p in (p0, p1, p2):\n"
        "        resolve_update(p)\n"),
    "W201": (
        "\n\n@jax.jit\n"
        "def _photonlint_canary_jit(x):\n"
        "    return x * time.time()\n"),
    "W301": (
        "\n\ndef _photonlint_canary_donate(buf):\n"
        "    fn = jax.jit(lambda b: b + 1, donate_argnums=(0,))\n"
        "    out = fn(buf)\n"
        "    return out + buf\n"),
    "W401": (
        "\n\ndef _photonlint_canary_fault():\n"
        "    fault_point(\"canary.unlisted\")\n"),
    "W501": (
        "\n\ndef _photonlint_canary_schema(snap):\n"
        "    return snap[\"photonlint_canary_missing_key\"]\n"),
    "W203": (
        "\n\n@jax.jit\n"
        "def _photonlint_canary_callback(x):\n"
        "    jax.experimental.io_callback(print, None, x)\n"
        "    return x\n"),
    "W601": (
        "\n\ndef _photonlint_canary_axis(x):\n"
        "    return jax.lax.psum(x, \"photonlint_bogus_axis\")\n"),
    "W701": (
        "\n\n@jax.jit\n"
        "def _photonlint_canary_kernel(v):\n"
        "    return v * 2\n"
        "\n\ndef _photonlint_canary_retrace(xs):\n"
        "    n = len(xs)\n"
        "    return _photonlint_canary_kernel(jnp.zeros(n))\n"),
}


@pytest.fixture(scope="module")
def seeded_package(tmp_path_factory):
    """A copy of the real package with one violation per family seeded
    into game/coordinate_descent.py (which already imports jnp, jax,
    time and fault_point)."""
    root = tmp_path_factory.mktemp("canary")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(README, root / "README.md")
    target = root / "photon_ml_tpu" / "game" / "coordinate_descent.py"
    with open(target, "a") as fh:
        for snippet in CANARIES.values():
            fh.write(snippet)
    return root


def test_obs_export_drift_canary(tmp_path_factory):
    """The live-plane fault point rides the same bidirectional W4xx
    reconcile as every other point: renaming its README PHOTON_FAULTS
    row makes the REAL ``obs/export.py`` call sites fire W401
    (undocumented site) AND the now-phantom row fire W402 (row without
    a site) — so the telemetry exporter cannot drift out of the
    operator-facing fault table unnoticed."""
    root = tmp_path_factory.mktemp("obs_export_canary")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    readme_text = (REPO_ROOT / "README.md").read_text()
    assert "| `obs.export` |" in readme_text, \
        "README PHOTON_FAULTS table lost its obs.export row"
    (root / "README.md").write_text(readme_text.replace(
        "| `obs.export` |", "| `obs.export.phantom` |"))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE)
    w401 = [f for f in report.new if f.rule == "W401"
            and '"obs.export"' in f.message]
    assert w401, "no W401 for the undocumented obs.export call sites"
    assert all(f.path == "photon_ml_tpu/obs/export.py" for f in w401)
    w402 = [f for f in report.new if f.rule == "W402"
            and "obs.export.phantom" in f.message]
    assert w402, "no W402 for the phantom obs.export README row"


def test_canaries_turn_the_run_red(seeded_package):
    report = runner.lint(
        seeded_package, paths=["photon_ml_tpu"],
        readme=seeded_package / "README.md", baseline=BASELINE)
    fired = {f.rule for f in report.new}
    missing = set(CANARIES) - fired
    assert not missing, (
        f"rule families failed to fire on seeded violations: "
        f"{sorted(missing)}; fired={sorted(fired)}")
    # and every canary is attributed to the seeded file
    seeded = [f for f in report.new
              if f.rule in CANARIES]
    assert all(f.path == "photon_ml_tpu/game/coordinate_descent.py"
               for f in seeded)


# -- W8xx precision dtype-flow ----------------------------------------------

W801_POSITIVE = """
import jax
import jax.numpy as jnp

def total_loss(per_example, a, b):
    acts = per_example.astype(jnp.bfloat16)
    total = jnp.sum(acts)                      # W801: bf16 sum, no acc
    lhs = a.astype(jnp.bfloat16)
    rhs = b.astype(jnp.bfloat16)
    prod = lhs @ rhs                           # W801: bf16 matmul
    return total, prod
"""

W801_NEGATIVE = """
import jax
import jax.numpy as jnp

def total_loss(per_example, a, b):
    acts = per_example.astype(jnp.bfloat16)
    total = jnp.sum(acts, dtype=jnp.float32)       # explicit accumulator
    upcast = jnp.sum(acts.astype(jnp.float32))     # upcast clears taint
    lhs = a.astype(jnp.bfloat16)
    rhs = b.astype(jnp.bfloat16)
    prod = jax.lax.dot_general(
        lhs, rhs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # f32 accumulation
    kept = jnp.sum(per_example)                    # unknown dtype: clean
    return total, upcast, prod, kept
"""

W801_SUPPRESSED = """
import jax.numpy as jnp

def total_loss(per_example):
    acts = per_example.astype(jnp.bfloat16)
    # photonlint: allow-W801(fixture: bf16 partial sum re-reduced in f32)
    return jnp.sum(acts)
"""


def test_w801_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W801_POSITIVE},
                         families={"W8"})
    assert [f.rule for f in report.new] == ["W801", "W801"]


def test_w801_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W801_NEGATIVE},
                         families={"W8"})
    assert report.new == []


def test_w801_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W801_SUPPRESSED},
                         families={"W8"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W801"]


W802_POSITIVE = """
import jax
import jax.numpy as jnp

@jax.jit
def kernel(x):
    scale = jnp.asarray(1.0, dtype=jnp.float64)    # W802: f64 under jit
    return x * scale
"""

W802_NEGATIVE = """
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

@jax.jit
def kernel(x):
    scale = jnp.asarray(1.0, dtype=jnp.float64)    # guarded: x64 enabled
    return x * scale

def host_accumulate(xs):
    return jnp.asarray(xs, dtype=jnp.float32)
"""

W802_SUPPRESSED = """
import jax
import jax.numpy as jnp

@jax.jit
def kernel(x):
    # photonlint: allow-W802(fixture: caller asserts x64 mode at startup)
    scale = jnp.asarray(1.0, dtype=jnp.float64)
    return x * scale
"""


def test_w802_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W802_POSITIVE},
                         families={"W8"})
    assert [f.rule for f in report.new] == ["W802"]


def test_w802_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W802_NEGATIVE},
                         families={"W8"})
    assert report.new == []


def test_w802_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W802_SUPPRESSED},
                         families={"W8"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W802"]


W803_POSITIVE = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x):
    return x * 2

def run(v):
    host = np.asarray(kernel(v))
    return kernel(host)            # W803: round-trip re-enters jit
"""

W803_NEGATIVE = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x):
    return x * 2

def run(v):
    host = np.asarray(kernel(v))
    np.save("/tmp/x.npy", host)    # host-side consumption only
    return kernel(jnp.asarray(host, dtype=jnp.float32))  # explicit dtype
"""

W803_SUPPRESSED = """
import jax
import numpy as np

@jax.jit
def kernel(x):
    return x * 2

def run(v):
    host = np.asarray(kernel(v))
    # photonlint: allow-W803(fixture: dtype identical by construction)
    return kernel(host)
"""


def test_w803_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W803_POSITIVE},
                         families={"W8"})
    assert [f.rule for f in report.new] == ["W803"]


def test_w803_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W803_NEGATIVE},
                         families={"W8"})
    assert report.new == []


def test_w803_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W803_SUPPRESSED},
                         families={"W8"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W803"]


W804_POSITIVE = """
import jax.numpy as jnp

def loss_fn(preds, targets):
    p16 = preds.astype(jnp.bfloat16)
    t32 = targets.astype(jnp.float32)
    return p16 - t32               # W804: implicit promotion in loss path
"""

W804_NEGATIVE = """
import jax.numpy as jnp

def loss_fn(preds, targets):
    p = preds.astype(jnp.float32)  # explicit cast: the decision is visible
    t = targets.astype(jnp.float32)
    return p - t

def combine(a, b):
    lo = a.astype(jnp.bfloat16)
    hi = b.astype(jnp.float32)
    return lo * hi                 # not a loss/grad path: quiet
"""

W804_SUPPRESSED = """
import jax.numpy as jnp

def loss_fn(preds, targets):
    p16 = preds.astype(jnp.bfloat16)
    t32 = targets.astype(jnp.float32)
    # photonlint: allow-W804(fixture: promotion to f32 is the intent)
    return p16 - t32
"""


def test_w804_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W804_POSITIVE},
                         families={"W8"})
    assert [f.rule for f in report.new] == ["W804"]


def test_w804_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W804_NEGATIVE},
                         families={"W8"})
    assert report.new == []


def test_w804_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W804_SUPPRESSED},
                         families={"W8"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W804"]


# -- W9xx host-concurrency safety -------------------------------------------

W901_POSITIVE = """
import threading

class Worker:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._count = 0

    def start(self):
        self._thread.start()

    def _run(self):
        while True:
            self._count += 1       # W901: thread write, unlocked reader

    def snapshot(self):
        return self._count

    def stop(self):
        self._thread.join()
"""

W901_NEGATIVE = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._count = 0

    def start(self):
        self._thread.start()

    def _run(self):
        while True:
            with self._lock:
                self._count += 1

    def snapshot(self):
        with self._lock:
            return self._count

    def stop(self):
        self._thread.join()
"""

W901_SUPPRESSED = """
import threading

class Worker:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._count = 0

    def start(self):
        self._thread.start()

    def _run(self):
        while True:
            # photonlint: allow-W901(fixture: int store is atomic enough here)
            self._count += 1

    def snapshot(self):
        return self._count

    def stop(self):
        self._thread.join()
"""


def test_w901_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W901_POSITIVE},
                         families={"W9"})
    assert [f.rule for f in report.new] == ["W901"]
    assert "_count" in report.new[0].message


def test_w901_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W901_NEGATIVE},
                         families={"W9"})
    assert report.new == []


def test_w901_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W901_SUPPRESSED},
                         families={"W9"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W901"]


W901_GUARD_POSITIVE = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._values = {}

    def inc(self, key):
        self._values[key] = self._values.get(key, 0) + 1   # W901: bare

    def total(self):
        with self._lock:
            return sum(self._values.values())
"""


def test_w901_inconsistent_guard_positive(tmp_path):
    """The other W901 shape: a lock guards reads of an attribute while a
    write elsewhere skips it."""
    report = run_fixture(tmp_path, {"mod.py": W901_GUARD_POSITIVE},
                         families={"W9"})
    assert [f.rule for f in report.new] == ["W901"]
    assert "_values" in report.new[0].message


W902_POSITIVE = """
import signal
import time

class Latch:
    def install(self):
        signal.signal(signal.SIGTERM, self._on_signal)

    def _on_signal(self, signum, frame):
        time.sleep(0.1)            # W902: not async-signal-safe
"""

W902_NEGATIVE = """
import os
import signal
import threading

class Latch:
    def __init__(self):
        self._event = threading.Event()

    def install(self):
        signal.signal(signal.SIGTERM, self._on_signal)

    def _on_signal(self, signum, frame):
        self._event.set()
        os.kill(os.getpid(), signum)
"""

W902_SUPPRESSED = """
import signal
import time

class Latch:
    def install(self):
        signal.signal(signal.SIGTERM, self._on_signal)

    def _on_signal(self, signum, frame):
        # photonlint: allow-W902(fixture: test-only handler, never installed in prod)
        time.sleep(0.1)
"""


def test_w902_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W902_POSITIVE},
                         families={"W9"})
    assert [f.rule for f in report.new] == ["W902"]
    assert "time.sleep" in report.new[0].message


def test_w902_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W902_NEGATIVE},
                         families={"W9"})
    assert report.new == []


def test_w902_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W902_SUPPRESSED},
                         families={"W9"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W902"]


W903_POSITIVE = """
import threading

class Pump:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()       # W903: never joined

    def _run(self):
        pass
"""

W903_NEGATIVE = """
import threading

class Pump:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def close(self):
        self._thread.join()

    def _run(self):
        pass
"""

W903_SUPPRESSED = """
import threading

class Pump:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        # photonlint: allow-W903(fixture: process-lifetime daemon by design)
        self._thread.start()

    def _run(self):
        pass
"""


def test_w903_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W903_POSITIVE},
                         families={"W9"})
    assert [f.rule for f in report.new] == ["W903"]
    assert "_thread" in report.new[0].message


def test_w903_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W903_NEGATIVE},
                         families={"W9"})
    assert report.new == []


def test_w903_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W903_SUPPRESSED},
                         families={"W9"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W903"]


W904_POSITIVE = """
import threading

class Pair:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()

    def one(self):
        with self._la:
            with self._lb:
                pass

    def two(self):
        with self._lb:
            with self._la:         # W904: reversed nesting
                pass
"""

W904_NEGATIVE = """
import threading

class Pair:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()

    def one(self):
        with self._la:
            with self._lb:
                pass

    def two(self):
        with self._la:
            with self._lb:
                pass
"""


def test_w904_positive(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W904_POSITIVE},
                         families={"W9"})
    assert [f.rule for f in report.new] == ["W904"]


def test_w904_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W904_NEGATIVE},
                         families={"W9"})
    assert report.new == []


def test_w904_suppressed(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": W904_POSITIVE},
                         families={"W9"})
    assert len(report.new) == 1
    line = report.new[0].line
    src = W904_POSITIVE.splitlines()
    src.insert(line - 1,
               "            # photonlint: allow-W904"
               "(fixture: methods never run concurrently)")
    report = run_fixture(tmp_path, {"mod.py": "\n".join(src) + "\n"},
                         families={"W9"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["W904"]


# -- W8xx / W9xx seeded canaries --------------------------------------------

def test_w801_seeded_pallas_accumulator_deletion(tmp_path_factory):
    """Deleting ``preferred_element_type=jnp.float32`` from the pallas
    margin matmul must fire W801 on a scratch copy — the f32-accumulator
    convention is enforced, not just commented."""
    root = tmp_path_factory.mktemp("pallas_acc")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    target = root / "photon_ml_tpu" / "ops" / "pallas_kernels.py"
    src = target.read_text()
    needle = (
        "    return jax.lax.dot_general(\n"
        "        X, col, (((1,), (0,)), ((), ())),\n"
        "        precision=precision,\n"
        "        preferred_element_type=jnp.float32).reshape(-1)\n")
    assert needle in src, "pallas margin matmul moved; update this test"
    target.write_text(src.replace(needle, (
        "    return jax.lax.dot_general(\n"
        "        X, col, (((1,), (0,)), ((), ())),\n"
        "        precision=precision).reshape(-1)\n")))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         families={"W8"})
    w801 = [f for f in report.new if f.rule == "W801"
            and f.path == "photon_ml_tpu/ops/pallas_kernels.py"]
    assert w801, [f.format() for f in report.new]


def test_w901_seeded_metrics_lock_deletion(tmp_path_factory):
    """Deleting the ``with self._lock:`` acquire around Counter.inc's
    write must fire W901 on a scratch copy."""
    root = tmp_path_factory.mktemp("metrics_lock")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    target = root / "photon_ml_tpu" / "obs" / "metrics.py"
    src = target.read_text()
    needle = ("        with self._lock:\n"
              "            self._values[key] = "
              "self._values.get(key, 0) + n\n")
    assert needle in src, "Counter.inc moved; update this test"
    target.write_text(src.replace(needle, (
        "        self._values[key] = self._values.get(key, 0) + n\n")))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         families={"W9"})
    w901 = [f for f in report.new if f.rule == "W901"
            and f.path == "photon_ml_tpu/obs/metrics.py"]
    assert w901, [f.format() for f in report.new]
    assert "_values" in w901[0].message


def test_w902_seeded_preempt_sleep_insertion(tmp_path_factory):
    """A ``time.sleep`` added to the preempt SIGTERM latch handler must
    fire W902 on a scratch copy — the async-signal-safety of
    utils/preempt.py is enforced, not assumed."""
    root = tmp_path_factory.mktemp("preempt_sleep")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    target = root / "photon_ml_tpu" / "utils" / "preempt.py"
    src = target.read_text()
    needle = "    def _on_signal(self, signum, frame) -> None:\n"
    assert needle in src, "preempt._on_signal moved; update this test"
    target.write_text(src.replace(
        needle, needle + "        time.sleep(0.5)\n"))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         families={"W9"})
    w902 = [f for f in report.new if f.rule == "W902"
            and f.path == "photon_ml_tpu/utils/preempt.py"]
    assert w902, [f.format() for f in report.new]
    assert "time.sleep" in w902[0].message


def test_exemplars_clean_without_suppressions():
    """pallas_kernels.py and preempt.py must be clean BY CONSTRUCTION —
    zero W8xx/W9xx findings and zero suppression directives."""
    for rel in ("photon_ml_tpu/ops/pallas_kernels.py",
                "photon_ml_tpu/utils/preempt.py"):
        assert "photonlint:" not in (REPO_ROOT / rel).read_text(), \
            f"{rel} must not need suppressions"
    report = runner.lint(REPO_ROOT, paths=["photon_ml_tpu"],
                         families={"W8", "W9"}, baseline=None)
    hits = [f for f in report.new
            if f.path in ("photon_ml_tpu/ops/pallas_kernels.py",
                          "photon_ml_tpu/utils/preempt.py")]
    assert hits == [], [f.format() for f in hits]


def test_changed_files_filter_keeps_whole_program_resolution(tmp_path):
    """changed_paths restricts the report, not the analysis: the same
    fixture reports its W801 when its file is in the changed set and
    nothing when only the other file is."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "hot.py").write_text(W801_POSITIVE)
    (pkg / "cold.py").write_text("x = 1\n")
    report = runner.lint(tmp_path, paths=["pkg"], families={"W8"},
                         changed_paths={"pkg/hot.py"})
    assert [f.rule for f in report.new] == ["W801", "W801"]
    report = runner.lint(tmp_path, paths=["pkg"], families={"W8"},
                         changed_paths={"pkg/cold.py"})
    assert report.new == []


def test_w801_seeded_qpsum_dequant_downgrade(tmp_path_factory):
    """Downcasting the qpsum dequant buffer to bf16 while dropping the
    sum's ``dtype=jnp.float32`` accumulator must fire W801 on a scratch
    copy — the f32-accumulate contract of the quantized collectives is
    enforced, not just promised in the module docstring."""
    root = tmp_path_factory.mktemp("qpsum_acc")
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    target = (root / "photon_ml_tpu" / "parallel"
              / "quantized_collectives.py")
    src = target.read_text()
    needle = (
        "    total = jnp.sum(dequantize_blockwise(q_all, scale_all), "
        "axis=0,\n"
        "                    dtype=jnp.float32)\n")
    assert needle in src, "qpsum dequant-sum moved; update this test"
    target.write_text(src.replace(needle, (
        "    deq = dequantize_blockwise(q_all, scale_all)"
        ".astype(jnp.bfloat16)\n"
        "    total = jnp.sum(deq, axis=0)\n")))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         families={"W8"})
    w801 = [f for f in report.new if f.rule == "W801"
            and f.path == ("photon_ml_tpu/parallel/"
                           "quantized_collectives.py")]
    assert w801, [f.format() for f in report.new]


def test_quantized_collectives_clean_without_suppressions():
    """The quantized collective wrappers must pass the collective-axis
    (W6xx) and precision (W8xx) families clean BY CONSTRUCTION — zero
    findings AND zero suppression directives in the source."""
    rel = "photon_ml_tpu/parallel/quantized_collectives.py"
    assert "photonlint:" not in (REPO_ROOT / rel).read_text(), \
        f"{rel} must not need suppressions"
    report = runner.lint(REPO_ROOT, paths=["photon_ml_tpu"],
                         families={"W6", "W8"}, baseline=None)
    hits = [f for f in report.new if f.path == rel]
    assert hits == [], [f.format() for f in hits]


# -- WAxx wire-protocol drift ------------------------------------------------

WA_CLIENT_SCORE_PROBE = """
class Client:
    def request(self, msg):
        return msg

    def score(self, rows):
        return self.request({"kind": "score", "rows": rows})

    def probe(self):
        return self.request({"kind": "probe"})
"""

WA_SERVER_SCORE_ONLY = """
def serve_loop(recv, send):
    msg = recv()
    kind = msg.get("kind")
    if kind == "score":
        send({"kind": "scores", "rows": msg.get("rows")})
"""

WA_SERVER_SCORE_PROBE = """
def serve_loop(recv, send):
    msg = recv()
    kind = msg.get("kind")
    if kind == "score":
        send({"kind": "scores", "rows": msg.get("rows")})
    elif kind == "probe":
        send({"kind": "pong"})
"""

WA_CLIENT_SCORE_ONLY = """
class Client:
    def request(self, msg):
        return msg

    def score(self, rows):
        return self.request({"kind": "score", "rows": rows})
"""


def test_wa01_positive(tmp_path):
    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_PROBE,
                   "serve/server.py": WA_SERVER_SCORE_ONLY},
        families={"WA"})
    assert rules_of(report) == ["WA01"], [f.format() for f in report.new]
    (f,) = report.new
    assert '"probe"' in f.message
    assert f.path == "pkg/serve/client.py", "WA01 names the SEND site"


def test_wa01_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_PROBE,
                   "serve/server.py": WA_SERVER_SCORE_PROBE},
        families={"WA"})
    assert report.new == [], [f.format() for f in report.new]


def test_wa01_suppressed(tmp_path):
    client = WA_CLIENT_SCORE_PROBE.replace(
        'return self.request({"kind": "probe"})',
        'return self.request({"kind": "probe"})  '
        '# photonlint: allow-WA01(fixture: probe handler lands next PR)')
    report = run_fixture(
        tmp_path, {"serve/client.py": client,
                   "serve/server.py": WA_SERVER_SCORE_ONLY},
        families={"WA"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WA01"]


def test_wa02_positive(tmp_path):
    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_ONLY,
                   "serve/server.py": WA_SERVER_SCORE_PROBE},
        families={"WA"})
    assert rules_of(report) == ["WA02"], [f.format() for f in report.new]
    (f,) = report.new
    assert '"probe"' in f.message
    assert f.path == "pkg/serve/server.py", "WA02 names the dead handler"


def test_wa02_negative(tmp_path):
    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_ONLY,
                   "serve/server.py": WA_SERVER_SCORE_ONLY},
        families={"WA"})
    assert report.new == []


def test_wa02_suppressed(tmp_path):
    server = WA_SERVER_SCORE_PROBE.replace(
        '    elif kind == "probe":',
        '    # photonlint: allow-WA02(fixture: probe client lands next'
        ' PR)\n    elif kind == "probe":')
    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_ONLY,
                   "serve/server.py": server},
        families={"WA"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WA02"]


def test_wa00_dynamic_kind(tmp_path):
    src = """
def emit(client, kinds):
    for k in kinds:
        client.request({"kind": k})
"""
    report = run_fixture(tmp_path, {"serve/emit.py": src},
                         families={"WA"})
    assert "WA00" in rules_of(report), [f.format() for f in report.new]
    suppressed = src.replace(
        'client.request({"kind": k})',
        'client.request({"kind": k})  '
        '# photonlint: allow-WA00(fixture: kinds come from a test list)')
    report = run_fixture(tmp_path, {"serve/emit.py": suppressed},
                         families={"WA"})
    assert report.new == []


def test_wa00_literal_prefix_is_not_dynamic(tmp_path):
    src = """
def emit(client, n):
    client.request({"kind": f"score_b{n}"})


def serve_loop(recv, send):
    msg = recv()
    kind = msg.get("kind")
    if kind == "score_b4":
        send({"kind": "scores"})
"""
    report = run_fixture(tmp_path, {"serve/mod.py": src},
                         families={"WA"})
    assert report.new == [], [f.format() for f in report.new]


WA03_PROTOCOL = """
class ServeRequestError(RuntimeError):
    pass


class ShedError(ServeRequestError):
    pass


class BoomError(ServeRequestError):
    pass


_TYPED_ERRORS = {
    "BoomError": BoomError,
}


def typed_error(resp):
    err = resp.get("error")
    if err is None:
        return None
    name = err.partition(":")[0]
    if name in _TYPED_ERRORS:
        return _TYPED_ERRORS[name](err)
    return ServeRequestError(err)


def fail(shard):
    raise BoomError(f"shard {shard} down")
"""


def test_wa03_positive(tmp_path):
    proto = WA03_PROTOCOL.replace('    "BoomError": BoomError,\n', '')
    report = run_fixture(tmp_path, {"serve/protocol.py": proto},
                         families={"WA"})
    wa03 = [f for f in report.new if f.rule == "WA03"]
    assert wa03, [f.format() for f in report.new]
    assert "BoomError" in wa03[0].message
    assert "raise BoomError" in (
        tmp_path / "pkg/serve/protocol.py").read_text().splitlines()[
            wa03[0].line - 1], "WA03 fires at the raise site"


def test_wa03_negative(tmp_path):
    report = run_fixture(tmp_path, {"serve/protocol.py": WA03_PROTOCOL},
                         families={"WA"})
    assert [f for f in report.new if f.rule == "WA03"] == [], \
        [f.format() for f in report.new]


def test_wa03_suppressed(tmp_path):
    proto = WA03_PROTOCOL.replace(
        '    "BoomError": BoomError,\n', '').replace(
        '    raise BoomError(f"shard {shard} down")',
        '    # photonlint: allow-WA03(fixture: parsed by a sidecar, not'
        ' typed_error)\n'
        '    raise BoomError(f"shard {shard} down")')
    report = run_fixture(tmp_path, {"serve/protocol.py": proto},
                         families={"WA"})
    assert [f for f in report.new if f.rule == "WA03"] == []
    assert "WA03" in [f.rule for f in report.suppressed]


WA04_FIXTURE = """
_TRANSPORT_REPLY_ERRORS = frozenset({
    "OSError",
    "GhostFault",
})


def run(sock, send):
    try:
        return sock.read()
    except OSError as e:
        send({"kind": "error", "error": f"{type(e).__name__}: {e}"})
"""


def test_wa04_positive(tmp_path):
    report = run_fixture(tmp_path, {"serve/fleet.py": WA04_FIXTURE},
                         families={"WA"})
    wa04 = [f for f in report.new if f.rule == "WA04"]
    assert len(wa04) == 1, [f.format() for f in report.new]
    assert "GhostFault" in wa04[0].message
    assert wa04[0].path == "pkg/serve/fleet.py"


def test_wa04_negative(tmp_path):
    src = WA04_FIXTURE.replace('    "GhostFault",\n', '')
    report = run_fixture(tmp_path, {"serve/fleet.py": src},
                         families={"WA"})
    assert report.new == [], [f.format() for f in report.new]


def test_wa04_python3_alias_is_unreachable(tmp_path):
    """The exact PR 19 real finding: ``IOError`` aliases ``OSError`` in
    Python 3, so ``type(e).__name__`` can never render it."""
    src = WA04_FIXTURE.replace('"GhostFault"', '"IOError"')
    report = run_fixture(tmp_path, {"serve/fleet.py": src},
                         families={"WA"})
    wa04 = [f for f in report.new if f.rule == "WA04"]
    assert len(wa04) == 1 and "IOError" in wa04[0].message, \
        [f.format() for f in report.new]


def test_wa04_suppressed(tmp_path):
    src = WA04_FIXTURE.replace(
        '    "GhostFault",',
        '    "GhostFault",  # photonlint: allow-WA04(fixture: emitted '
        'by an out-of-tree member build)')
    report = run_fixture(tmp_path, {"serve/fleet.py": src},
                         families={"WA"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WA04"]


WA05_FIXTURE = """
def hello_msg():
    return {"kind": "hello", "proto": 1, "model_id": "m0"}


def read_hello(recv):
    msg = recv()
    if msg.get("kind") == "hello":
        return msg.get("generation")
"""


def test_wa05_positive(tmp_path):
    report = run_fixture(tmp_path, {"serve/proto.py": WA05_FIXTURE},
                         families={"WA"})
    wa05 = [f for f in report.new if f.rule == "WA05"]
    assert len(wa05) == 1, [f.format() for f in report.new]
    assert '"generation"' in wa05[0].message
    assert '"hello"' in wa05[0].message


def test_wa05_negative(tmp_path):
    src = WA05_FIXTURE.replace('msg.get("generation")',
                               'msg.get("model_id")')
    report = run_fixture(tmp_path, {"serve/proto.py": src},
                         families={"WA"})
    assert report.new == [], [f.format() for f in report.new]


def test_wa05_open_writer_exempt(tmp_path):
    """A ``**spread`` writer is an open field set — reads of its kind
    cannot be judged and must not fire."""
    src = """
def hello_msg(extra):
    return {"kind": "hello", "proto": 1, **extra}


def read_hello(recv):
    msg = recv()
    if msg.get("kind") == "hello":
        return msg.get("generation")
"""
    report = run_fixture(tmp_path, {"serve/proto.py": src},
                         families={"WA"})
    assert report.new == [], [f.format() for f in report.new]


def test_wa05_suppressed(tmp_path):
    src = WA05_FIXTURE.replace(
        '        return msg.get("generation")',
        '        # photonlint: allow-WA05(fixture: field lands with the'
        ' v2 hello)\n'
        '        return msg.get("generation")')
    report = run_fixture(tmp_path, {"serve/proto.py": src},
                         families={"WA"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WA05"]


# -- WBxx telemetry-taxonomy drift -------------------------------------------

WB_EMIT_AND_STATUS = """
def work(registry):
    registry.counter("hits").inc(tier="hot")
    registry.counter("misses").inc(tier="hot")


def status(totals):
    return totals.get("hits")
"""

WB_README_TAXONOMY = """# fixture

| metric | type | where | labels |
|--------|------|-------|--------|
| `hits` | counter | work | `tier` |
| `misses` | counter | work | `tier` |
"""


def test_wb01_positive(tmp_path):
    readme = WB_README_TAXONOMY.replace(
        "| `misses` | counter | work | `tier` |\n", "")
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         readme=readme, families={"WB"})
    wb01 = [f for f in report.new if f.rule == "WB01"]
    assert len(wb01) == 1, [f.format() for f in report.new]
    assert '"misses"' in wb01[0].message
    assert wb01[0].path == "pkg/mod.py", "WB01 fires at the emit site"


def test_wb01_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         readme=WB_README_TAXONOMY, families={"WB"})
    assert report.new == [], [f.format() for f in report.new]


def test_wb01_no_table_no_reconcile(tmp_path):
    """A README without a metric taxonomy table skips WB01/WB02 — the
    reconcile is gated on the table existing, exactly like W401's."""
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                        readme="# fixture readme, no tables\n",
                        families={"WB"})
    assert report.new == [], [f.format() for f in report.new]


def test_wb01_suppressed(tmp_path):
    src = WB_EMIT_AND_STATUS.replace(
        '    registry.counter("misses").inc(tier="hot")',
        '    # photonlint: allow-WB01(fixture: row lands with the'
        ' dashboard PR)\n'
        '    registry.counter("misses").inc(tier="hot")')
    readme = WB_README_TAXONOMY.replace(
        "| `misses` | counter | work | `tier` |\n", "")
    report = run_fixture(tmp_path, {"mod.py": src}, readme=readme,
                         families={"WB"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WB01"]


def test_wb02_positive(tmp_path):
    readme = WB_README_TAXONOMY + "| `ghost` | counter | nowhere | — |\n"
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         readme=readme, families={"WB"})
    wb02 = [f for f in report.new if f.rule == "WB02"]
    assert len(wb02) == 1, [f.format() for f in report.new]
    assert "`ghost`" in wb02[0].message
    assert wb02[0].path == "README.md"
    assert wb02[0].line == len(readme.splitlines())


def test_wb02_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         readme=WB_README_TAXONOMY, families={"WB"})
    assert report.new == []


def test_wb02_baselined(tmp_path):
    """README findings have no source line to carry an inline
    directive, so a deliberate WB02 is grandfathered via the baseline
    (same workflow as any README-side finding)."""
    readme = WB_README_TAXONOMY + "| `ghost` | counter | nowhere | — |\n"
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(WB_EMIT_AND_STATUS)
    readme_path = tmp_path / "README.md"
    readme_path.write_text(readme)
    baseline = tmp_path / "baseline.json"
    n = runner.write_baseline(tmp_path, baseline, paths=["pkg"],
                              readme=readme_path, families={"WB"})
    assert n == 1
    report = runner.lint(tmp_path, paths=["pkg"], readme=readme_path,
                         baseline=baseline, families={"WB"})
    assert report.new == []
    assert [f.rule for f in report.baselined] == ["WB02"]


def test_wb03_positive(tmp_path):
    src = WB_EMIT_AND_STATUS.replace('totals.get("hits")',
                                     'totals.get("hit_total")')
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    wb03 = [f for f in report.new if f.rule == "WB03"]
    assert len(wb03) == 1, [f.format() for f in report.new]
    assert '"hit_total"' in wb03[0].message
    assert "totals.get" in (tmp_path / "pkg/mod.py").read_text(
        ).splitlines()[wb03[0].line - 1], "WB03 fires at the read site"


def test_wb03_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         families={"WB"})
    assert report.new == []


def test_wb03_span_name_compare(tmp_path):
    """Record-name comparisons (``rec.get("name") == ...``) are
    consumer reads too — of the span namespace."""
    src = """
import trace


def work():
    with trace.span("phase.run", step=1):
        pass


def scan(rec):
    return rec.get("name") == "phase.missing"
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    wb03 = [f for f in report.new if f.rule == "WB03"]
    assert len(wb03) == 1, [f.format() for f in report.new]
    assert '"phase.missing"' in wb03[0].message
    clean = src.replace('"phase.missing"', '"phase.run"')
    report = run_fixture(tmp_path, {"mod.py": clean}, families={"WB"})
    assert report.new == []


def test_wb03_prefix_emit_matches(tmp_path):
    """A literal-head f-string emit is a prefix family: consumers of
    any name under the prefix are satisfied, and no WB00 fires."""
    src = """
def work(registry, n):
    registry.counter(f"bucket_{n}").inc()


def status(totals):
    return totals.get("bucket_3")
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    assert report.new == [], [f.format() for f in report.new]


def test_wb03_suppressed(tmp_path):
    src = WB_EMIT_AND_STATUS.replace(
        '    return totals.get("hits")',
        '    # photonlint: allow-WB03(fixture: emitted by the sibling'
        ' service, not this package)\n'
        '    return totals.get("hit_total")')
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WB03"]


def test_wb04_positive(tmp_path):
    src = """
def a(registry):
    registry.counter("hits").inc(tier="hot")


def b(registry):
    registry.counter("hits").inc()
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    wb04 = [f for f in report.new if f.rule == "WB04"]
    assert len(wb04) == 1, [f.format() for f in report.new]
    assert '"hits"' in wb04[0].message and "tier" in wb04[0].message


def test_wb04_negative(tmp_path):
    report = run_fixture(tmp_path, {"mod.py": WB_EMIT_AND_STATUS},
                         families={"WB"})
    assert report.new == []


def test_wb04_suppressed(tmp_path):
    src = """
def a(registry):
    registry.counter("hits").inc(tier="hot")


def b(registry):
    # photonlint: allow-WB04(fixture: label-less fallback cold path)
    registry.counter("hits").inc()
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    assert report.new == []
    assert [f.rule for f in report.suppressed] == ["WB04"]


def test_wb00_loop_literal_span_table_resolved(tmp_path):
    """The stage-span table idiom — ``for name, ... in <literal tuple
    of tuples>`` feeding ``record_span(name, ...)`` — is statically
    auditable: no WB00, each row's name registers as an emit (constant
    slices respected), and a second loop reusing the same variable
    without a telemetry call contributes nothing."""
    src = """
import trace


def work(w):
    stage_spans = (
        ("stage.alpha", 1, 2),
        ("stage.beta", 2, 3),
        ("stage.gamma", 3, 4),
    )
    for name, s, e in stage_spans[1:]:
        trace.record_span(name, s, e, tag="x")
    events = []
    for name, s, e in stage_spans:
        events.append({"name": name})
    return events


def scan(rec):
    return rec.get("name") == "stage.beta"
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    assert report.new == [], [f.format() for f in report.new]
    # the sliced-away first row is NOT an emit: a consumer of it is a
    # phantom, proving resolution honors the [1:] slice
    orphan = src.replace('rec.get("name") == "stage.beta"',
                         'rec.get("name") == "stage.alpha"')
    report = run_fixture(tmp_path, {"mod.py": orphan}, families={"WB"})
    assert rules_of(report) == ["WB03"], \
        [f.format() for f in report.new]
    assert '"stage.alpha"' in report.new[0].message


def test_wb00_dynamic_name(tmp_path):
    src = """
def work(registry, name):
    registry.counter(name).inc()
"""
    report = run_fixture(tmp_path, {"mod.py": src}, families={"WB"})
    assert rules_of(report) == ["WB00"], [f.format() for f in report.new]
    suppressed = src.replace(
        "    registry.counter(name).inc()",
        "    # photonlint: allow-WB00(fixture: names come from operator"
        " config)\n"
        "    registry.counter(name).inc()")
    report = run_fixture(tmp_path, {"mod.py": suppressed},
                         families={"WB"})
    assert report.new == []


# -- WA/WB canaries on the real package --------------------------------------

def _package_copy(tmp_path_factory, name):
    root = tmp_path_factory.mktemp(name)
    shutil.copytree(
        REPO_ROOT / "photon_ml_tpu", root / "photon_ml_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(README, root / "README.md")
    return root


def test_wa01_canary_renamed_dispatch_kind(tmp_path_factory):
    """Renaming the ``score`` dispatch arm (service AND router — both
    dispatch it) leaves the real client send sites orphaned: WA01 must
    name the ``ServeClient.score`` send site in protocol.py."""
    root = _package_copy(tmp_path_factory, "wa01_canary")
    for rel in ("photon_ml_tpu/serve/service.py",
                "photon_ml_tpu/serve/router.py"):
        path = root / rel
        src = path.read_text()
        assert 'elif kind == "score":' in src, f"{rel} lost its score arm"
        path.write_text(src.replace('elif kind == "score":',
                                    'elif kind == "score_v9":'))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE,
                         families={"WA"})
    wa01 = [f for f in report.new if f.rule == "WA01"
            and '"score"' in f.message]
    assert wa01, [f.format() for f in report.new]
    assert any(f.path == "photon_ml_tpu/serve/protocol.py"
               for f in wa01), "WA01 must name the client send site"
    # ...and the now-senderless arms fire the other direction
    assert [f for f in report.new if f.rule == "WA02"
            and '"score_v9"' in f.message]


def test_wa03_canary_typed_error_dropped_from_table(tmp_path_factory):
    """Deleting ``ShardUnavailableError`` from ``typed_error()``'s
    table downgrades the fleet's shard-unavailable refusal to a generic
    error on the client: WA03 must fire at the fleet raise site."""
    root = _package_copy(tmp_path_factory, "wa03_canary")
    proto = root / "photon_ml_tpu" / "serve" / "protocol.py"
    src = proto.read_text()
    entry = '    "ShardUnavailableError": ShardUnavailableError,\n'
    assert entry in src, "protocol.py lost its typed-error table entry"
    proto.write_text(src.replace(entry, ""))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE,
                         families={"WA"})
    wa03 = [f for f in report.new if f.rule == "WA03"
            and "ShardUnavailableError" in f.message]
    assert wa03, [f.format() for f in report.new]
    assert all(f.path == "photon_ml_tpu/serve/fleet.py" for f in wa03)


def test_wb03_canary_renamed_emit_orphans_router_read(tmp_path_factory):
    """Renaming the ``serve_route`` counter at its fleet emit site
    orphans the router's ``by_label`` stats read — the silent-dashboard
    bug class WB03 exists for."""
    root = _package_copy(tmp_path_factory, "wb03_canary")
    fleet = root / "photon_ml_tpu" / "serve" / "fleet.py"
    src = fleet.read_text()
    emit = 'self._registry.counter("serve_route").inc(outcome=outcome)'
    assert emit in src, "fleet.py lost its serve_route emit"
    fleet.write_text(src.replace(
        emit,
        'self._registry.counter("serve_route_v2").inc(outcome=outcome)'))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE,
                         families={"WB"})
    wb03 = [f for f in report.new if f.rule == "WB03"
            and '"serve_route"' in f.message]
    assert wb03, [f.format() for f in report.new]
    assert any(f.path == "photon_ml_tpu/serve/router.py" for f in wb03)
    # the renamed emit is also undocumented + its README row phantom
    assert [f for f in report.new if f.rule == "WB01"
            and "serve_route_v2" in f.message]
    assert [f for f in report.new if f.rule == "WB02"
            and "serve_route" in f.message]


def test_wb03_canary_photon_status_aux_read(tmp_path_factory):
    """tools/photon_status.py is loaded as an AUXILIARY consumer: after
    renaming the ``serve_rows_scored`` emit in scoring.py, WB03 must
    fire at the photon_status totals read — outside the lint path set."""
    root = _package_copy(tmp_path_factory, "wb03_aux_canary")
    (root / "tools").mkdir()
    shutil.copy(REPO_ROOT / "tools" / "photon_status.py",
                root / "tools" / "photon_status.py")
    scoring = root / "photon_ml_tpu" / "serve" / "scoring.py"
    src = scoring.read_text()
    assert '"serve_rows_scored"' in src
    scoring.write_text(src.replace('"serve_rows_scored"',
                                   '"serve_rows_scored_v2"'))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE,
                         families={"WB"})
    wb03 = [f for f in report.new if f.rule == "WB03"
            and '"serve_rows_scored"' in f.message]
    assert wb03, [f.format() for f in report.new]
    assert any(f.path == "tools/photon_status.py" for f in wb03)


def test_wbxx_canary_renamed_queue_wait_span(tmp_path_factory):
    """Renaming the batcher's ``serve.queue_wait`` span emit orphans
    three corners at once: photon_status's per-request queue-wait fold
    goes silently dark (WB03 at the aux consumer), the renamed emit is
    undocumented (WB01 at the batcher), and the README taxonomy row
    turns phantom (WB02)."""
    root = _package_copy(tmp_path_factory, "wb_queue_wait_canary")
    (root / "tools").mkdir()
    shutil.copy(REPO_ROOT / "tools" / "photon_status.py",
                root / "tools" / "photon_status.py")
    batcher = root / "photon_ml_tpu" / "serve" / "batcher.py"
    src = batcher.read_text()
    assert '"serve.queue_wait"' in src, "batcher lost its span emit"
    batcher.write_text(src.replace('"serve.queue_wait"',
                                   '"serve.queue_wait_v2"'))
    report = runner.lint(root, paths=["photon_ml_tpu"],
                         readme=root / "README.md", baseline=BASELINE,
                         families={"WB"})
    wb03 = [f for f in report.new if f.rule == "WB03"
            and '"serve.queue_wait"' in f.message]
    assert wb03, [f.format() for f in report.new]
    assert any(f.path == "tools/photon_status.py" for f in wb03)
    wb01 = [f for f in report.new if f.rule == "WB01"
            and "serve.queue_wait_v2" in f.message]
    assert wb01 and all(
        f.path == "photon_ml_tpu/serve/batcher.py" for f in wb01)
    assert [f for f in report.new if f.rule == "WB02"
            and "`serve.queue_wait`" in f.message]


# -- incremental cache -------------------------------------------------------

WB_SECOND_MODULE = """
def more(registry):
    registry.counter("extra").inc()
"""


def test_cache_replay_is_identical_and_invalidates_on_edit(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(WB_EMIT_AND_STATUS)
    (pkg / "b.py").write_text(WB_SECOND_MODULE)
    cache_dir = tmp_path / "cache"

    cold = runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                       cache_dir=cache_dir)
    assert cold.cache_stats["file_misses"] == 2
    assert not cold.cache_stats["program_hit"]

    warm = runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                       cache_dir=cache_dir)
    assert warm.cache_stats["program_hit"]
    assert warm.format_json() == cold.format_json(), \
        "replayed findings must be byte-identical"

    # touch-without-edit (same bytes, fresh mtime): still a full hit
    (pkg / "a.py").write_text(WB_EMIT_AND_STATUS)
    touched = runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                          cache_dir=cache_dir)
    assert touched.cache_stats["program_hit"], \
        "content-keyed cache must ignore mtimes"

    # a real edit: program replay misses, ONE file reloads, findings
    # match a from-scratch run exactly
    (pkg / "a.py").write_text(WB_EMIT_AND_STATUS.replace(
        'totals.get("hits")', 'totals.get("hit_total")'))
    edited = runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                         cache_dir=cache_dir)
    assert not edited.cache_stats["program_hit"]
    assert edited.cache_stats["file_hits"] == 1
    assert edited.cache_stats["file_misses"] == 1
    fresh = runner.lint(tmp_path, paths=["pkg"], families={"WB"})
    assert edited.format_json() == fresh.format_json(), \
        "cached partial rerun must equal a cold run"
    assert [f.rule for f in edited.new] == ["WB03"]


def test_cache_invalidates_when_analyzer_changes(tmp_path, monkeypatch):
    from photon_ml_tpu.analysis import cache as cache_mod

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(WB_EMIT_AND_STATUS)
    cache_dir = tmp_path / "cache"
    runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                cache_dir=cache_dir)
    # simulate an edited analyzer: every key must change
    monkeypatch.setattr(cache_mod, "_analyzer_sig", "different-digest")
    report = runner.lint(tmp_path, paths=["pkg"], families={"WB"},
                         cache_dir=cache_dir)
    assert not report.cache_stats["program_hit"]
    assert report.cache_stats["file_misses"] == 1


def test_cli_stats_and_cache_replay(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(WB_EMIT_AND_STATUS)
    (tmp_path / "README.md").write_text("# fixture\n")
    cli = [sys.executable, str(REPO_ROOT / "tools" / "photonlint.py"),
           "pkg", "--root", str(tmp_path), "--no-baseline",
           "--readme", str(tmp_path / "README.md"),
           "--cache-dir", str(tmp_path / "cache"), "--stats"]
    first = subprocess.run(cli, capture_output=True, text=True)
    assert first.returncode == 0, first.stdout + first.stderr
    assert "photonlint: timing WB:" in first.stderr
    assert "1 miss(es)" in first.stderr
    second = subprocess.run(cli, capture_output=True, text=True)
    assert second.returncode == 0, second.stdout + second.stderr
    assert "program replay" in second.stderr
    assert second.stdout == first.stdout, \
        "cached CLI output must be byte-identical"


def test_cli_list_rules_covers_wa_wb():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "photonlint.py"),
         "--list-rules"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0
    for rule_id in ("WA00", "WA01", "WA02", "WA03", "WA04", "WA05",
                    "WB00", "WB01", "WB02", "WB03", "WB04"):
        assert f"{rule_id}  " in proc.stdout, f"{rule_id} missing"


def test_sarif_golden_fixture(tmp_path):
    """Pin the full SARIF document — rules array (all families,
    including WA/WB, with helpUri catalog anchors) and a result — to a
    committed golden. Regenerate deliberately when the catalog grows:
    the diff IS the review artifact."""
    from photon_ml_tpu.analysis.sarif import to_sarif

    report = run_fixture(
        tmp_path, {"serve/client.py": WA_CLIENT_SCORE_PROBE,
                   "serve/server.py": WA_SERVER_SCORE_ONLY},
        families={"WA"})
    doc = to_sarif(report)
    golden = json.loads(
        (REPO_ROOT / "tests" / "goldens" / "sarif_golden.json")
        .read_text())
    assert doc == golden, (
        "SARIF output drifted from tests/goldens/sarif_golden.json — "
        "if the change is deliberate, regenerate the golden")
