"""Pallas SSD chunked-scan kernel vs sequential-recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.mamba import ssd_chunked


def _mk(B, S, H, P, N, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), dtype)
    return x, dt, A, Bm, Cm


SWEEP = [
    # (B, S, H, P, N, chunk)
    (1, 128, 2, 64, 128, 128),
    (2, 256, 4, 64, 128, 128),
    (1, 256, 2, 32, 64, 64),
    (2, 96, 2, 64, 128, 32),   # S not a multiple of 128 (pad path)
    (1, 200, 3, 16, 32, 64),   # odd everything
]


@pytest.mark.parametrize("shape", SWEEP)
def test_ssd_kernel_matches_sequential(shape):
    B, S, H, P, N, chunk = shape
    x, dt, A, Bm, Cm = _mk(B, S, H, P, N)
    y, state = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, state_ref = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), atol=2e-4, rtol=2e-4
    )
    # padded tail contributes dt=0 no-ops, so states agree too
    np.testing.assert_allclose(
        np.asarray(state), np.asarray(state_ref), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    x, dt, A, Bm, Cm = _mk(1, 128, 2, 64, 64, dtype=dtype)
    y, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=64)
    y_ref, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_model_chunked_path_matches_sequential():
    """The model's pure-jnp chunked SSD (dry-run path) is also validated."""
    x, dt, A, Bm, Cm = _mk(2, 128, 4, 32, 64)
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4, rtol=2e-4)


def test_model_chunked_path_grads_finite_with_fast_decay():
    """Full-width decay rates (A down to -16) over 128-long chunks push
    exp(cum_i - cum_j) past f32 range above the diagonal; the masked
    entries must not turn the gradient into NaN."""
    x, dt, A, Bm, Cm = _mk(1, 256, 2, 16, 32)
    A = jnp.asarray([-16.0, -1.0])
    dt = dt * 5.0  # up to 0.5: |cum| over a chunk reaches ~1000

    def loss(x, dt, Bm, Cm):
        y, s = ssd_chunked(x, dt, A, Bm, Cm, chunk=128)
        return (y**2).sum() + (s**2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, Bm, Cm)
    for g in grads:
        assert bool(jnp.isfinite(g).all())
    y1, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=128)
    y2, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=2e-4)


def test_state_enables_continuation():
    """final_state after S1 tokens == init_state for the next S2 tokens."""
    x, dt, A, Bm, Cm = _mk(1, 256, 2, 32, 64)
    y_full, s_full = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    _, s_half = ops.ssd_scan(
        x[:, :128], dt[:, :128], A, Bm[:, :128], Cm[:, :128], chunk=64
    )
    y2, s2 = ref.ssd_scan_ref(
        x[:, 128:], dt[:, 128:], A, Bm[:, 128:], Cm[:, 128:],
        init_state=s_half,
    )
    np.testing.assert_allclose(
        np.asarray(y2), np.asarray(y_full[:, 128:]), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(s2), np.asarray(s_full), atol=2e-4, rtol=2e-4
    )
