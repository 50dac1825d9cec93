"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU-native structure: the grid is ``(batch*heads, n_chunks)`` with the
chunk axis declared "arbitrary" (sequential) — TPU executes the last grid
dimension in order, so the inter-chunk SSM state lives in a VMEM scratch
buffer that persists across chunk iterations (the standard Pallas carry
idiom).  Per chunk the kernel computes, entirely in VMEM:

  1. the intra-chunk quadratic term  (C B^T ⊙ decay) x  — MXU matmuls on
     [Q, N] x [N, Q] and [Q, Q] x [Q, P] tiles (Q = chunk = 128 aligned);
  2. the contribution of the carried state  C (exp(cum) h);
  3. the state update  h <- exp(cum_Q) h + (decay_to_end * dt * B)^T x.

The in-chunk prefix sum of ``dt * A`` is a masked lane/sublane reduction
over a lower-triangular ``[Q, Q]`` mask (Mosaic has no cumsum).  ``dt``
arrives twice, as a ``[Q, 1]`` column and a ``[1, Q]`` row, so the kernel
needs no transpose.  B and C are shared by all heads of a batch row and
are read through the index map, not copied per head.

One (batch, head) pair per grid row keeps the working set
(Q x max(N, P, Q) fp32 tiles + the [N, P] state) well under VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _ssd_kernel(
    x_ref,  # [Q, P]
    dtc_ref,  # [Q, 1] dt as a column
    dtr_ref,  # [1, Q] dt as a row
    a_ref,  # [1, 1]
    b_ref,  # [Q, N]
    c_ref,  # [Q, N]
    y_ref,  # [Q, P] out
    state_ref,  # [N, P] out (final state; written every chunk)
    h_scratch,  # [N, P] f32 VMEM scratch (persists across chunk steps)
):
    ci = pl.program_id(1)
    Q = x_ref.shape[0]

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[...].astype(jnp.float32)  # [Q,P]
    A = a_ref[...]  # [1,1]
    dA_col = dtc_ref[...] * A  # [Q,1]
    dt_row = dtr_ref[...]  # [1,Q]
    dA_row = dt_row * A  # [1,Q]
    Bm = b_ref[...].astype(jnp.float32)  # [Q,N]
    Cm = c_ref[...].astype(jnp.float32)

    # inclusive prefix sums of dA in both layouts
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = iota_j <= iota_i  # [i, j]: j <= i
    cum_col = jnp.sum(jnp.where(tri, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(
        jnp.where(iota_i <= iota_j, dA_col, 0.0), axis=0, keepdims=True
    )
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # [1,1] = cum at Q-1

    # (1) intra-chunk: W[i,j] = (C_i.B_j) exp(cum_i - cum_j) dt_j, j <= i
    CB = _dot(Cm, Bm, ((1,), (1,)))  # [Q,Q]
    W = jnp.where(tri, CB * jnp.exp(cum_col - cum_row) * dt_row, 0.0)
    y = _dot(W, x, ((1,), (0,)))  # [Q,P]

    # (2) contribution of the carried state
    h = h_scratch[...]  # [N,P]
    y += _dot(Cm * jnp.exp(cum_col), h, ((1,), (0,)))

    # (3) state update: h <- exp(cum_Q) h + sum_j exp(cum_Q-cum_j) dt_j B_j x_j
    Bw = Bm * (jnp.exp(total - cum_col) * dtc_ref[...])  # [Q,N]
    new_h = jnp.exp(total) * h + _dot(Bw, x, ((0,), (0,)))  # [N,P]
    h_scratch[...] = new_h

    y_ref[...] = y.astype(y_ref.dtype)
    state_ref[...] = new_h


def ssd_scan_pallas(
    x: jnp.ndarray,  # [B, S, H, P]
    dt: jnp.ndarray,  # [B, S, H]
    A: jnp.ndarray,  # [H]
    Bm: jnp.ndarray,  # [B, S, N]
    Cm: jnp.ndarray,  # [B, S, N]
    chunk: int = 128,
    *,
    interpret: bool,
):
    """Returns (y [B,S,H,P], final_state [B,H,N,P])."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, "pad sequence to a chunk multiple (ops.py does)"
    nc = S // chunk

    # Layout: fold (B, H) into grid axis 0; chunk axis is sequential.
    xr = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtr = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(B * H, 1, S)
    dtc = dtr.reshape(B * H, S, 1)
    ar = jnp.broadcast_to(
        A.astype(jnp.float32)[None, :], (B, H)
    ).reshape(B * H, 1, 1)

    y, state = pl.pallas_call(
        _ssd_kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, 1, chunk), lambda h, c: (h, 0, c)),
            pl.BlockSpec((None, 1, 1), lambda h, c: (h, 0, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h // H, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda h, c: (h // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, P), lambda h, c: (h, c, 0)),
            # final state: every chunk writes the same [N,P] block; the
            # last (sequential) write wins.
            pl.BlockSpec((None, N, P), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xr, dtc, dtr, ar, Bm, Cm)

    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    state = state.reshape(B, H, N, P)
    return y, state
