"""Core layers: RMSNorm, RoPE, GQA attention (qk-norm / sliding-window /
streaming), gated & plain MLP.  Pure JAX, pytree params.

Conventions:
  x            [B, S, D]
  q            [B, S, H, K]      (K = head_dim)
  k, v         [B, T, G, K]      (G = kv heads)
  attn scores  [B, G, Hg, S, T]  (Hg = H // G)

All weights live in ``cfg.dtype`` (bf16 on TPU); softmax, norms and losses
accumulate in fp32.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

Params = Dict[str, Any]

_NEG_INF = -1e30
# Above this query length the attention uses the q-chunked streaming path
# so the S x T score buffer stays bounded (flash-attention-style, pure XLA).
STREAM_THRESHOLD = 8192
STREAM_CHUNK = 1024


def dtype_of(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# norms / rope
# --------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """Rotary embedding. x: [B,S,H,K]; positions: [S] or [B,S]."""
    K = x.shape[-1]
    half = K // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        angles = positions.astype(jnp.float32)[None, :, None] * freqs[None, None, :]
        angles = angles[:, :, None, :]  # [1,S,1,half]
    else:
        angles = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half : 2 * half]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    out = jnp.concatenate([rx1, rx2, x[..., 2 * half :]], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ArchConfig) -> Params:
    D, H, G, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = D ** -0.5
    scale_out = (H * K) ** -0.5
    p: Params = {
        "wq": (jax.random.normal(k1, (D, H, K)) * scale_in).astype(dt),
        "wk": (jax.random.normal(k2, (D, G, K)) * scale_in).astype(dt),
        "wv": (jax.random.normal(k3, (D, G, K)) * scale_in).astype(dt),
        "wo": (jax.random.normal(k4, (H, K, D)) * scale_out).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((K,), dtype=jnp.float32)
        p["k_norm"] = jnp.ones((K,), dtype=jnp.float32)
    return p


def _mask_bias(
    q_pos: jnp.ndarray,  # [Sq] shared, or [B,Sq] per-row
    kv_pos: jnp.ndarray,  # [T]
    causal: bool,
    window: Optional[int],
) -> jnp.ndarray:
    """Additive mask (0 = attend, -inf = blocked): [Sq, T] for shared
    positions, [B, Sq, T] when each batch row queries its own position
    (batched serving decode)."""
    qp = q_pos[..., :, None]  # [Sq,1] or [B,Sq,1]
    # ring-buffer slots not yet written carry -1
    ok = jnp.broadcast_to(kv_pos >= 0, qp.shape[:-1] + kv_pos.shape)
    if causal:
        ok = ok & (kv_pos <= qp)
    if window is not None:
        ok = ok & (kv_pos > qp - window)
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def _attend_dense(
    q: jnp.ndarray,  # [B,Sq,G,Hg,K]
    k: jnp.ndarray,  # [B,T,G,K]
    v: jnp.ndarray,
    bias: jnp.ndarray,  # [Sq,T] or [B,Sq,T]
    scale: float,
) -> jnp.ndarray:
    if bias.ndim == 2:
        bias = bias[None]
    scores = jnp.einsum(
        "bsghk,btgk->bghst", q, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bghst,btgk->bsghk",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


def multi_head_attention(
    q: jnp.ndarray,  # [B,Sq,H,K]
    k: jnp.ndarray,  # [B,T,G,K]
    v: jnp.ndarray,  # [B,T,G,K]
    q_pos: jnp.ndarray,  # [Sq] (or [B,Sq] per-row) query positions
    kv_pos: jnp.ndarray,  # [T]  absolute positions of the keys (-1 = empty)
    causal: bool,
    window: Optional[int],
    scale: Optional[float] = None,  # None: head_dim ** -0.5
) -> jnp.ndarray:
    """GQA attention with optional causality/sliding window; scores are
    scaled by ``scale``.

    Long query sequences are processed in chunks with lax.scan so the score
    buffer is O(chunk x T) rather than O(S x T) — this is the pure-XLA
    analogue of the Pallas flash-attention kernel (kernels/flash_attention).
    """
    B, Sq, H, K = q.shape
    G = k.shape[2]
    qg = q.reshape(B, Sq, G, H // G, K)
    if scale is None:
        scale = K ** -0.5

    if Sq <= STREAM_THRESHOLD:
        bias = _mask_bias(q_pos, kv_pos, causal, window)
        out = _attend_dense(qg, k, v, bias, scale)
        return out.reshape(B, Sq, H, K)

    assert q_pos.ndim == 1, "streaming path is prefill-only (shared q_pos)"
    n_chunks = Sq // STREAM_CHUNK
    assert Sq % STREAM_CHUNK == 0, "query length must divide STREAM_CHUNK"
    qg_c = qg.reshape(B, n_chunks, STREAM_CHUNK, G, H // G, K)
    qpos_c = q_pos.reshape(n_chunks, STREAM_CHUNK)

    def body(_, inp):
        qc, qp = inp
        bias = _mask_bias(qp, kv_pos, causal, window)
        return None, _attend_dense(qc, k, v, bias, scale)

    _, out = jax.lax.scan(
        body, None, (jnp.moveaxis(qg_c, 1, 0), qpos_c)
    )
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, K)
    return out


def apply_attention(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,  # [B,S,D]
    q_pos: jnp.ndarray,  # [S] absolute positions
    cache: Optional[Params] = None,
    cache_index: Optional[jnp.ndarray] = None,
    self_attend: bool = True,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Attention sublayer.

    ``cache`` given + ``self_attend``  : prefill — attend over the local
        k/v (streaming path for long S) and write them into the cache.
    ``cache`` given + not self_attend  : decode — write the new k/v at
        ``cache_index`` (ring slot for SWA) and attend over the buffer.
    no cache                           : training — plain self-attention.

    RoPE is applied unless ``cfg.rope_theta`` is None (NoPE); scores are
    scaled by ``cfg.attn_scale`` (default ``head_dim ** -0.5``).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta is not None:
        q = rope(q, q_pos, cfg.rope_theta)
        k = rope(k, q_pos, cfg.rope_theta)

    window = cfg.sliding_window
    new_cache = None
    if cache is not None:
        W = cache["k"].shape[1]  # buffer length (ring if SWA)
        S = k.shape[1]
        if cache_index is not None and cache_index.ndim == 1:
            # Per-row decode (batched serving: rows at different depths in
            # one batch).  Each row writes its single new k/v at its own
            # slot; the shared ``pos`` leaf stays consistent because with
            # no sliding window slot == absolute position for every row,
            # and different rows writing the same slot write the same
            # position value.  Ring wrap breaks that invariant, so the
            # serving engine rejects SWA configs up front.
            assert window is None, (
                "per-row cache positions require sliding_window=None"
            )
            slots = cache_index.astype(jnp.int32)  # [B]
            bidx = jnp.arange(k.shape[0])
            ck = cache["k"].at[bidx, slots].set(
                k[:, 0].astype(cache["k"].dtype)
            )
            cv = cache["v"].at[bidx, slots].set(
                v[:, 0].astype(cache["v"].dtype)
            )
            cpos = cache["pos"].at[0, slots].set(
                q_pos[:, 0].astype(jnp.int32)
            )
        elif S >= W:
            # Prefill overflowing a ring buffer: keep the last W entries.
            # Ring-slot invariant (slot == pos % W) needs S % W == 0.
            assert S % W == 0, "SWA prefill length must be a multiple of W"
            ck = k[:, -W:].astype(cache["k"].dtype)
            cv = v[:, -W:].astype(cache["v"].dtype)
            cpos = q_pos[-W:].astype(jnp.int32)[None, :]
        else:
            slot = (
                cache_index % W
                if window is not None
                else cache_index
            )
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)
            )
            cpos = jax.lax.dynamic_update_slice(
                cache["pos"], q_pos.astype(jnp.int32)[None, :], (0, slot)
            )
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    if cache is None or self_attend:
        out = multi_head_attention(
            q, k, v, q_pos, q_pos, cfg.causal, window, cfg.attn_scale
        )
    else:
        out = multi_head_attention(
            q, new_cache["k"], new_cache["v"], q_pos, new_cache["pos"][0],
            cfg.causal, window, cfg.attn_scale,
        )
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache


def init_attn_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype
) -> Params:
    W = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
    G, K = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, W, G, K), dtype=dtype),
        "v": jnp.zeros((batch, W, G, K), dtype=dtype),
        # -1 marks unwritten slots; kept 2-D [1, W] so every cache leaf has
        # a leading batch-like axis (simplifies sharding rules).
        "pos": -jnp.ones((1, W), dtype=jnp.int32),
    }


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def init_mlp(key: jax.Array, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    """An MLP of width ``d_ff`` (default ``cfg.d_ff``)."""
    D, F = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {
        "w_up": (jax.random.normal(k1, (D, F)) * D**-0.5).astype(dt),
        "w_down": (jax.random.normal(k2, (F, D)) * F**-0.5).astype(dt),
    }
    if cfg.mlp_gated:
        p["w_gate"] = (jax.random.normal(k3, (D, F)) * D**-0.5).astype(dt)
    return p


def apply_mlp(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if cfg.mlp_gated:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------


def init_embedding(key: jax.Array, cfg: ArchConfig) -> Params:
    V, D = cfg.padded_vocab, cfg.d_model
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    p: Params = {"tokens": (jax.random.normal(k1, (V, D)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(k2, (D, V)) * D**-0.5).astype(dt)
    return p


def embed_tokens(p: Params, cfg: ArchConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    h = jnp.take(p["tokens"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return h


def unembed(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tokens"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def cross_entropy(
    logits: jnp.ndarray,  # [B,S,V]
    labels: jnp.ndarray,  # [B,S] (-1 = ignore)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom, denom
