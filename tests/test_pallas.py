"""Pallas fused value+gradient kernel vs the two-pass XLA formulation.

Runs in interpreter mode on CPU: this checks the kernel's arithmetic for
every loss and for ragged edge tiles, not that Mosaic accepts it (that is
tests/test_tpu_compile.py, for a described chip) nor that it is right or
fast on a chip (that is chip_smoke.py, run on one).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.ops.losses import LOSSES, get_loss
from photon_ml_tpu.ops.pallas_kernels import (
    _xla_sums as _xla_sums_kernelmod,
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
    assert not pallas_supported(1 << 20, 1024, jnp.float32)
    assert not pallas_supported(1 << 20, 1024, jnp.bfloat16)


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
    import jax

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
