"""The Pallas fused kernel's two forms (value+gradient, Hessian-vector)
vs the two-pass XLA formulation.

Runs in interpreter mode on CPU: this checks the kernels' arithmetic for
every loss and for ragged edge tiles, not that Mosaic accepts them (that is
tests/test_tpu_compile.py, for a described chip) nor that they are right or
fast on a chip (that is chip_smoke.py and the benchmark, run on one).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import DenseBatch, dense_batch
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops import pallas_kernels
from photon_ml_tpu.ops.aggregators import GLMObjective, hessian_vector
from photon_ml_tpu.ops.losses import LOSSES, get_loss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.pallas_kernels import (
    MIN_PALLAS_DIM,
    _xla_sums as _xla_sums_kernelmod,
    fused_hessian_vector_sums,
    fused_value_gradient_sums,
    pallas_supported,
)


def _case(n, d, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    off = (rng.normal(size=n) * 0.1).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    w = (rng.normal(size=d) * 0.05).astype(np.float32)
    return X, y, off, wt, w


def _xla_sums(loss, X, y, off, wt, w, shift):
    z = X @ w + off + shift
    l, d1 = loss.loss_and_d1(jnp.asarray(z), jnp.asarray(y))
    r = wt * np.asarray(d1)
    return (float(np.sum(wt * np.asarray(l))), r @ X, float(np.sum(r)))


@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_fused_matches_xla(loss_name):
    loss = get_loss(loss_name)
    X, y, off, wt, w = _case(700, 128)  # 700: ragged edge tile
    shift = 0.31
    v, vec, pre = fused_value_gradient_sums(
        loss, True, jnp.asarray(X), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(wt), jnp.asarray(w), jnp.float32(shift))
    v_ref, vec_ref, pre_ref = _xla_sums(loss, X, y, off, wt, w, shift)
    assert float(v) == pytest.approx(v_ref, rel=2e-5)
    assert float(pre) == pytest.approx(pre_ref, rel=2e-5, abs=1e-4)
    np.testing.assert_allclose(np.asarray(vec), vec_ref, rtol=2e-4,
                               atol=2e-4)


def test_exact_tile_multiple():
    loss = get_loss("logistic")
    X, y, off, wt, w = _case(1024, 256, seed=1)
    v, vec, pre = fused_value_gradient_sums(
        loss, True, jnp.asarray(X), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(wt), jnp.asarray(w), jnp.float32(0.0))
    v_ref, vec_ref, pre_ref = _xla_sums(loss, X, y, off, wt, w, 0.0)
    assert float(v) == pytest.approx(v_ref, rel=2e-5)
    np.testing.assert_allclose(np.asarray(vec), vec_ref, rtol=2e-4,
                               atol=2e-4)


def test_gate_disabled_on_cpu():
    # Tests run on CPU, so the production gate must refuse (interpret mode
    # is only for testing).
    for form in MIN_PALLAS_DIM:
        assert not pallas_supported(form, 1 << 20, 1024, jnp.float32)
        assert not pallas_supported(form, 1 << 20, 1024, jnp.bfloat16)


def test_fused_bf16_matches_f32_reference():
    """bf16 X (half the HBM stream) with f32 accumulators: sums must land
    within bf16 input-rounding distance of the f32 two-pass reference."""
    loss = get_loss("logistic")
    X, y, off, wt, w = _case(700, 128, seed=3)
    v, vec, pre = fused_value_gradient_sums(
        loss, True, jnp.asarray(X, jnp.bfloat16), jnp.asarray(y),
        jnp.asarray(off), jnp.asarray(wt), jnp.asarray(w),
        jnp.float32(0.1))
    assert v.dtype == jnp.float32 and vec.dtype == jnp.float32
    v_ref, vec_ref, pre_ref = _xla_sums(loss, X, y, off, wt, w, 0.1)
    assert float(v) == pytest.approx(v_ref, rel=2e-2)
    assert float(pre) == pytest.approx(pre_ref, rel=5e-2, abs=0.5)
    np.testing.assert_allclose(np.asarray(vec), vec_ref, rtol=5e-2,
                               atol=0.5)


def test_custom_vjp_differentiable():
    """jax.grad through the fused sums must work (falls back to the XLA
    formulation in the backward pass)."""
    loss = get_loss("logistic")
    X, y, off, wt, w = _case(300, 64, seed=2)

    def value_of(wv):
        v, _, _ = fused_value_gradient_sums(
            loss, True, jnp.asarray(X), jnp.asarray(y), jnp.asarray(off),
            jnp.asarray(wt), wv, jnp.float32(0.0))
        return v

    g = jax.grad(value_of)(jnp.asarray(w))
    # analytic gradient = vector_sum
    _, vec_ref, _ = _xla_sums(loss, X, y, off, wt, w, 0.0)
    np.testing.assert_allclose(np.asarray(g), vec_ref, rtol=2e-4, atol=2e-4)


@pytest.fixture
def gate_forced_open(as_on_one_tpu, monkeypatch):
    """``aggregators`` takes the fused forms as on one chip at a real size,
    the kernels in interpret mode (the program has no option for either).
    The width rule stays as it is: a batch under a form's
    ``MIN_PALLAS_DIM`` columns is two-pass here too."""
    monkeypatch.setattr(pallas_kernels, "MIN_PALLAS_ELEMENTS", 0)
    for name in ("fused_value_gradient_sums", "fused_hessian_vector_sums"):
        real = getattr(pallas_kernels, name)
        monkeypatch.setattr(
            pallas_kernels, name,
            lambda loss, interpret, *a, _real=real: _real(loss, True, *a))


def _hvp_norm(d, seed):
    rng = np.random.default_rng(seed)
    return NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, d), jnp.float32),
        shifts=jnp.asarray(rng.normal(size=d) * 0.3, jnp.float32))


def _lowerings(scope):
    counter = REGISTRY.counter("objective_lowerings")
    return {form: counter.value(scope=scope, form=form)
            for form in ("fused", "two_pass")}


def _fused_hessian_vector(loss, norm, w, v, batch):
    """``hessian_vector`` in the fused form: through the gate where the
    batch is wide enough for it (and then the count says it was taken), the
    kernel called as ``hessian_vector`` calls it where the width rule keeps
    the program off it: ragged tiles and odd widths are the kernel's own
    edge cases."""
    if batch.X.shape[1] >= MIN_PALLAS_DIM["hvp"]:
        before = _lowerings("objective.hvp")["fused"]
        out = hessian_vector(loss, norm, w, v, batch)
        assert _lowerings("objective.hvp")["fused"] == before + 1
        return out
    w_eff, margin_shift = norm.effective_coefficients(w)
    v_eff, v_shift = norm.effective_coefficients(v)
    return norm.reconstruct_gradient(*fused_hessian_vector_sums(
        loss, True, batch.X, batch.labels, batch.offsets, batch.weights,
        w_eff, margin_shift, v_eff, v_shift))


# id: loss, rows, cols, normalization, X's dtype, zero-weight tail, rtol
_HVP_CASES = {
    "logistic-at-the-width-rule": ("logistic", 1100, MIN_PALLAS_DIM["hvp"],
                                   True, "float32", 0, 2e-4),
    "logistic-ragged": ("logistic", 700, 128, False, "float32", 0, 2e-4),
    "squared-ragged": ("squared", 700, 128, False, "float32", 0, 2e-4),
    "poisson-ragged": ("poisson", 700, 128, False, "float32", 0, 2e-4),
    "logistic-exact-tiles": ("logistic", 1024, 256, False, "float32", 0,
                             2e-4),
    "logistic-two-ragged-tiles": ("logistic", 2500, 64, False, "float32", 0,
                                  2e-4),
    "logistic-odd-width": ("logistic", 1300, 65, True, "float32", 0, 2e-4),
    "logistic-factors-shifts": ("logistic", 700, 128, True, "float32", 0,
                                2e-4),
    "poisson-factors-shifts": ("poisson", 1300, 96, True, "float32", 0,
                               2e-4),
    "logistic-bf16": ("logistic", 700, 128, False, "bfloat16", 0, 5e-2),
    "squared-bf16-factors-shifts": ("squared", 700, 128, True, "bfloat16", 0,
                                    5e-2),
    "logistic-zero-weight-rows": ("logistic", 700, 128, True, "float32", 150,
                                  2e-4),
}


@pytest.mark.parametrize("case", sorted(_HVP_CASES))
def test_fused_hvp_matches_two_pass(case, gate_forced_open, monkeypatch):
    """``hessian_vector`` in the fused form against its own two-pass body,
    the reference semantics (float32 X in both; a bf16 X is held to bf16's
    input rounding of the f32 reference)."""
    loss_name, n, d, normalized, dtype, padded, rtol = _HVP_CASES[case]
    loss = get_loss(loss_name)
    X, y, off, wt, w = _case(n, d, seed=len(case))
    v = np.random.default_rng(5).normal(size=d).astype(np.float32)
    wt[n - padded:] = 0.0
    norm = _hvp_norm(d, 11) if normalized else NormalizationContext()
    fused = _fused_hessian_vector(
        loss, norm, jnp.asarray(w), jnp.asarray(v),
        dense_batch(X, y, off, wt, dtype=jnp.dtype(dtype)))
    assert fused.dtype == jnp.float32

    monkeypatch.setattr(pallas_kernels, "pallas_supported",
                        lambda *a, **kw: False)
    two_pass = hessian_vector(loss, norm, jnp.asarray(w), jnp.asarray(v),
                              dense_batch(X, y, off, wt))
    scale = float(jnp.max(jnp.abs(two_pass)))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_pass),
                               rtol=rtol, atol=rtol * scale)
    if padded:  # a zero-weight row adds nothing, whatever it holds
        X[n - padded:] = 1e6
        again = hessian_vector(loss, norm, jnp.asarray(w), jnp.asarray(v),
                               dense_batch(X, y, off, wt))
        np.testing.assert_allclose(np.asarray(again), np.asarray(two_pass),
                                   rtol=1e-6, atol=1e-6 * scale)


def _tron_solve(loss_name, X, y, off, wt):
    from photon_ml_tpu.optimize.tron import minimize_tron

    obj = GLMObjective(get_loss(loss_name), l2_lambda=0.1)
    batch = dense_batch(X, y, off, wt)
    x, hist, _ = minimize_tron(
        lambda w, p: p[0].calculate(w, p[1]),
        lambda w, v, p: p[0].hessian_vector(w, v, p[1]),
        jnp.zeros(X.shape[1], jnp.float32), (obj, batch), max_iter=30,
        tolerance=1e-4)
    return (np.asarray(x), int(np.asarray(hist.num_iterations)),
            int(np.asarray(hist.hvps).sum()))


@pytest.mark.parametrize("loss_name", ["squared", "logistic", "poisson"])
def test_tron_solve_with_fused_product_lands_on_two_pass_solve(
        loss_name, gate_forced_open, monkeypatch):
    """A whole trust-region solve whose every product (and evaluation) is
    the fused kernel, against the same solve in the two-pass forms, at the
    narrowest width the program takes both fused forms at. Column scales
    over a decade, as the benchmark's dense cells have: 3 iterations of
    9-14 conjugate-gradient steps. (A tolerance at float32's floor would
    test which side's last step rounding refuses, not the product.)"""
    d = max(MIN_PALLAS_DIM.values())
    X, y, off, wt, _ = _case(6 * d, d, seed=4)  # rows enough to condition it
    X = X * np.logspace(-0.5, 0.5, d).astype(np.float32)
    scopes = ("objective.hvp", "objective.value_and_grad")
    before = {scope: _lowerings(scope) for scope in scopes}
    x_fused, iters_fused, hvps_fused = _tron_solve(loss_name, X, y, off, wt)
    for scope in scopes:  # every call site of the solve took the kernel
        after = _lowerings(scope)
        assert after["fused"] > before[scope]["fused"], scope
        assert after["two_pass"] == before[scope]["two_pass"], scope
    monkeypatch.setattr(pallas_kernels, "pallas_supported",
                        lambda *a, **kw: False)
    x_ref, iters_ref, hvps_ref = _tron_solve(loss_name, X, y, off, wt)
    assert iters_ref >= 3 and hvps_ref >= 9 * iters_ref
    np.testing.assert_allclose(x_fused, x_ref, rtol=2e-4, atol=2e-4)
    assert abs(iters_fused - iters_ref) <= 1
    assert abs(hvps_fused - hvps_ref) <= 1


@pytest.mark.parametrize("n,d", [(2500, 64), (1300, 65), (700, 96)])
def test_fused_value_gradient_takes_narrow_and_odd_widths(n, d):
    """The program keeps a batch under ``MIN_PALLAS_DIM`` columns off a
    fused form because the form is slow there, not because it is wrong:
    the kernel itself takes any width (ragged row tiles, a width that fills
    no lane tile), against the two-pass sums. (``_HVP_CASES`` has the
    product's narrow cases.)"""
    loss = get_loss("logistic")
    X, y, off, wt, w = _case(n, d, seed=n + d)
    batch = dense_batch(X, y, off, wt)
    shift = jnp.float32(0.2)
    value, vec, pre = fused_value_gradient_sums(
        loss, True, batch.X, batch.labels, batch.offsets, batch.weights,
        jnp.asarray(w), shift)
    z = batch.margins(jnp.asarray(w), shift)
    r = batch.weights * loss.d1(z, batch.labels)
    assert float(value) == pytest.approx(
        float(jnp.sum(batch.weights * loss.loss(z, batch.labels))), rel=2e-5)
    want = np.asarray(batch.weighted_feature_sum(r))
    np.testing.assert_allclose(np.asarray(vec), want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())
    assert float(pre) == pytest.approx(float(jnp.sum(r)), rel=2e-4, abs=1e-3)


_TRACES = {"value_and_grad": lambda obj, w, b: obj.calculate(w, b),
           "hvp": lambda obj, w, b: obj.hessian_vector(w, w, b)}
# columns given the form's own rule, as on one chip, the form booked
_LOWERING_CASES = {
    "65-cols": (lambda rule: 65, True, "two_pass"),
    "one-under-the-rule": (lambda rule: rule - 1, True, "two_pass"),
    "at-the-rule": (lambda rule: rule, True, "fused"),
    "2048-cols": (lambda rule: 2048, True, "fused"),
    "2048-cols-on-the-cpu": (lambda rule: 2048, False, "two_pass"),
}


@pytest.mark.parametrize("traced", sorted(_TRACES))
@pytest.mark.parametrize("case", sorted(_LOWERING_CASES))
def test_objective_lowerings_books_the_form_traced(case, traced, request):
    """One count a trace of ``value_and_gradient`` / ``hessian_vector``, each
    by its own width rule: ``two_pass`` on the CPU and, on one chip, under
    the form's ``MIN_PALLAS_DIM`` columns (the sweep cells' 65-wide fixed
    effect); ``fused`` from there on (the dense cells' 2,048). The batch is
    traced at a real size (``MIN_PALLAS_ELEMENTS`` stands) and never run."""
    columns, on_one_tpu, form = _LOWERING_CASES[case]
    d = columns(MIN_PALLAS_DIM[traced])
    if on_one_tpu:
        request.getfixturevalue("as_on_one_tpu")
    n = -(-pallas_kernels.MIN_PALLAS_ELEMENTS // d)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    batch = DenseBatch(X=sds(n, d), labels=sds(n), offsets=sds(n),
                       weights=sds(n))
    obj = GLMObjective(get_loss("logistic"))
    scopes = ["objective." + name for name in _TRACES]
    before = {s: _lowerings(s) for s in scopes}
    jax.eval_shape(lambda w, b: _TRACES[traced](obj, w, b), sds(d), batch)
    booked = {s: {f: count - before[s][f]
                  for f, count in _lowerings(s).items()} for s in scopes}
    nothing = {"fused": 0, "two_pass": 0}
    assert booked == {
        s: dict(nothing, **{form: 1}) if s == "objective." + traced
        else nothing for s in scopes}
