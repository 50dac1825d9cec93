"""Pallas TPU flash-attention kernel (forward).

Online-softmax attention with GQA, causal masking, and sliding-window
support.  TPU-native design (not a CUDA port):

* the grid is ``(batch*heads, Sq/BQ, T/BK)``; the last axis walks the keys
  and is declared "arbitrary" (sequential), so one ``[BK, K]`` K/V tile at
  a time streams HBM->VMEM while the ``[BQ, K]`` query tile, the fp32
  accumulator and the running max/sum stay resident in VMEM scratch.  The
  working set is independent of T, so the kernel compiles at any length;
* GQA without copies: the K/V index map sends every query head of a group
  to its shared kv head (``h // Hg``);
* block shapes are MXU-aligned: BQ/BK multiples of 128 (sublane x lane
  8x128 tiling); the head dim is a full-extent block (any K);
* causal + window masking is computed from absolute positions so the same
  kernel serves train (full S x S), prefill and ring-buffer SWA layouts.

Validated against ref.py (pure jnp); see tests/test_kernels_flash.py for
the shape/dtype sweep and tests/test_tpu_compile.py for the v5e compile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [BQ, K]   queries of one (batch, head), one q block
    k_ref,  # [BK, K]   one key block of the matching kv head
    v_ref,  # [BK, K]
    qpos_ref,  # [BQ, 1] i32
    kpos_ref,  # [1, BK] i32
    o_ref,  # [BQ, K]
    acc_ref,  # [BQ, K] f32 scratch
    m_ref,  # [BQ, 1] f32 scratch: running max
    l_ref,  # [BQ, 1] f32 scratch: running sum
    *,
    causal: bool,
    window: Optional[int],
    sm_scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    v = v_ref[...]
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [BQ, BK]

    qpos = qpos_ref[...]  # [BQ,1]
    kpos = kpos_ref[...]  # [1,BK]
    ok = kpos >= 0
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_i = m_ref[...]
    m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_i - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Sq, H, K]
    k: jnp.ndarray,  # [B, T, G, K]
    v: jnp.ndarray,  # [B, T, G, K]
    q_pos: jnp.ndarray,  # [Sq] i32
    kv_pos: jnp.ndarray,  # [T] i32
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """pallas_call wrapper; ops.py chooses ``interpret`` from the backend."""
    B, Sq, H, K = q.shape
    T, G = k.shape[1], k.shape[2]
    Hg = H // G
    assert Sq % block_q == 0 and T % block_k == 0
    sm_scale = K**-0.5

    # Layout: fold (B, H) into the grid's first axis with heads of one kv
    # group adjacent, so query head h reads kv head h // Hg.
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, K)
    kr = k.transpose(0, 2, 1, 3).reshape(B * G, T, K)
    vr = v.transpose(0, 2, 1, 3).reshape(B * G, T, K)
    qpos2 = q_pos.reshape(Sq, 1).astype(jnp.int32)
    kpos2 = kv_pos.reshape(1, T).astype(jnp.int32)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, sm_scale=sm_scale
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, K), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, block_k, K), lambda h, i, j: (h // Hg, j, 0)),
            pl.BlockSpec((None, block_k, K), lambda h, i, j: (h // Hg, j, 0)),
            pl.BlockSpec((block_q, 1), lambda h, i, j: (i, 0)),
            pl.BlockSpec((1, block_k), lambda h, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, block_q, K), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, K), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, K), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr, qpos2, kpos2)

    return out.reshape(B, H, Sq, K).transpose(0, 2, 1, 3)
