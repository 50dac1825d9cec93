"""Mamba2 (state-space duality) mixer — pure-JAX chunked SSD reference.

Recurrence (per head h, state size N, head dim P):
    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t (x) x_t        (A < 0)
    y_t = C_t . h_t + D * x_t

The chunked algorithm (Dao & Gu 2024) splits the sequence into chunks of
``Q = cfg.ssm_chunk``: an intra-chunk quadratic term plus an inter-chunk
state recurrence carried by ``lax.scan``.  The Pallas kernel
(kernels/ssd_scan.py) mirrors exactly this structure; this module is its
oracle and the dry-run lowering path.

Single B/C group (G = 1) as in the Mamba2 default.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

Params = Dict[str, Any]


def init_mamba(key: jax.Array, cfg: ArchConfig) -> Params:
    D = cfg.d_model
    di = cfg.d_inner
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    kconv = cfg.ssm_conv
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    ch = di + 2 * N  # conv channels: x ++ B ++ C
    proj_out = 2 * di + 2 * N + H  # z ++ x ++ B ++ C ++ dt
    # dt bias: softplus^-1 of dt ~ U[1e-3, 1e-1]
    u = jax.random.uniform(keys[2], (H,), minval=1e-3, maxval=1e-1)
    dt_bias = jnp.log(jnp.expm1(u))
    return {
        "in_proj": (jax.random.normal(keys[0], (D, proj_out)) * D**-0.5).astype(dt),
        "conv_w": (jax.random.normal(keys[1], (kconv, ch)) * kconv**-0.5).astype(dt),
        "conv_b": jnp.zeros((ch,), dtype=dt),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(
            jax.random.uniform(keys[3], (H,), minval=1.0, maxval=16.0)
        ).astype(jnp.float32),
        "D": jnp.ones((H,), dtype=jnp.float32),
        "norm": jnp.ones((di,), dtype=jnp.float32),
        "out_proj": (jax.random.normal(keys[2], (di, D)) * di**-0.5).astype(dt),
    }


def _split_proj(cfg: ArchConfig, proj: jnp.ndarray):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di : di + di + 2 * N]
    dt = proj[..., di + di + 2 * N :]
    return z, xbc, dt


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv over seq. xbc: [B,S,Ch]; w: [k,Ch]."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        pad,
        w[:, None, :],  # [k, 1, Ch]
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=xbc.shape[-1],
    )
    return jax.nn.silu(out + b)


def ssd_chunked(
    x: jnp.ndarray,  # [B,S,H,P]
    dt: jnp.ndarray,  # [B,S,H]  (softplus applied)
    A: jnp.ndarray,  # [H]      (negative)
    Bm: jnp.ndarray,  # [B,S,N]
    Cm: jnp.ndarray,  # [B,S,N]
    chunk: int,
    init_state: Optional[jnp.ndarray] = None,  # [B,H,N,P]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B,S,H,P], final_state [B,H,N,P])."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    S_orig = S
    if S % chunk:
        # Zero-pad to a chunk multiple: dt == 0 entries are exact no-ops
        # (decay exp(0)=1, contribution dt*B*x = 0).
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // chunk
    xc = x.reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)

    dA = dtc * A  # [B,nc,Q,H], negative
    cum = jnp.cumsum(dA, axis=2)  # inclusive within-chunk cumsum

    # ---- intra-chunk (quadratic, masked) --------------------------------
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # [B,nc,Q,Q]
    # decay L[h,i,j] = exp(cum_i - cum_j), lower-triangular inclusive.
    # Mask before the exp: above the diagonal diff > 0 can overflow, and
    # an inf there would make the gradient 0 * inf = NaN.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Q,Q,H]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    W = CB[..., None] * L * dtc[:, :, None, :, :]  # [B,nc,Q(i),Q(j),H]
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", W, xc)

    # ---- chunk states ----------------------------------------------------
    cum_last = cum[:, :, -1:, :]  # [B,nc,1,H]
    decay_to_end = jnp.exp(cum_last - cum)  # [B,nc,Q,H]
    S_state = jnp.einsum(
        "bcjh,bcjn,bcjhp->bchnp", decay_to_end * dtc, Bc, xc
    )  # [B,nc,H,N,P]

    # ---- inter-chunk recurrence ------------------------------------------
    chunk_decay = jnp.exp(cum_last[:, :, 0, :])  # [B,nc,H]
    state0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((Bsz, H, N, P), jnp.float32)
    )

    def body(h, inp):
        s_c, dec_c, C_c, cum_c = inp
        # y from the incoming state: C_t . (exp(cum_t) h)
        y_off = jnp.einsum("bin,bhnp,bih->bihp", C_c, h, jnp.exp(cum_c))
        h = dec_c[:, :, None, None] * h + s_c
        return h, y_off

    xs = (
        jnp.moveaxis(S_state, 1, 0),
        jnp.moveaxis(chunk_decay, 1, 0),
        jnp.moveaxis(Cc, 1, 0),
        jnp.moveaxis(cum, 1, 0),
    )
    final_state, y_off = jax.lax.scan(body, state0, xs)
    y = y_diag + jnp.moveaxis(y_off, 0, 1)
    return y.reshape(Bsz, S, H, P)[:, :S_orig], final_state


def apply_mamba(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,  # [B,S,D]
    cache: Optional[Params] = None,
    return_cache: bool = False,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Mamba2 block. Training/prefill path (full sequence, chunked scan).

    If ``return_cache``, also returns {"conv": [B,k-1,Ch], "ssm": [B,H,P,N]}
    for subsequent decode steps: ``ssd_chunked``'s final state [B,H,N,P]
    transposed, the order ``apply_mamba_decode`` keeps it in.
    """
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"]).astype(x.dtype)
    xs = xbc[..., :di]
    Bm = xbc[..., di : di + N]
    Cm = xbc[..., di + N :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    xh = xs.reshape(*xs.shape[:2], H, P)
    from ..parallel import opt_flags

    if opt_flags.get("mamba_heads"):
        # §Perf: shard SSD heads over `model` so the chunked scan's big
        # [B, nc, Q, Q, H] intra-chunk buffers scale with TP degree.
        from jax.sharding import PartitionSpec as P_

        b = opt_flags.get("batch_axes")
        xh = jax.lax.with_sharding_constraint(xh, P_(b, None, "model", None))
        dt = jax.lax.with_sharding_constraint(dt, P_(b, None, "model"))
    y, final_state = ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(*x.shape[:2], di).astype(x.dtype)

    # gated RMSNorm then output projection
    from .layers import rms_norm

    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])

    new_cache = None
    if return_cache:
        k = cfg.ssm_conv
        # conv cache holds the last k-1 *pre-conv* xBC rows
        pre = jnp.einsum("bsd,de->bse", x, p["in_proj"])
        _, xbc_pre, _ = _split_proj(cfg, pre)
        conv_cache = xbc_pre[:, -(k - 1) :, :]
        new_cache = {"conv": conv_cache, "ssm": jnp.swapaxes(final_state, -1, -2)}
    return out, new_cache


def apply_mamba_decode(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,  # [B,1,D]
    cache: Params,
) -> Tuple[jnp.ndarray, Params]:
    """Single-token recurrent step (O(1) in sequence length).

    ``cache["ssm"]`` is the f32 state [B,H,P,N], stored and updated with
    N (the state size) minor.  The update is written as broadcast
    products and ``y`` as a sum over N, not as dot_generals, so the
    compiler keeps that layout (N=128 fills the lanes unpadded): a step
    reads each layer's state at most twice and writes it once, with no
    relayout.
    """
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    k = cfg.ssm_conv
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc_new, dt_raw = _split_proj(cfg, proj)

    window = jnp.concatenate([cache["conv"], xbc_new], axis=1)  # [B,k,Ch]
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)  # [B,1,Ch]

    xs = xbc[..., :di]
    Bm = xbc[..., di : di + N]
    Cm = xbc[..., di + N :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])[:, 0]  # [B,H]
    A = -jnp.exp(p["A_log"])

    xh = xs.reshape(-1, H, P).astype(jnp.float32)  # [B,H,P]
    h = cache["ssm"].astype(jnp.float32)  # [B,H,P,N]
    decay = jnp.exp(dt * A)  # [B,H]
    B1 = Bm[:, 0].astype(jnp.float32)  # [B,N]
    C1 = Cm[:, 0].astype(jnp.float32)  # [B,N]
    h = (decay[..., None, None] * h
         + (dt[..., None, None] * xh[..., :, None]) * B1[:, None, None, :])
    y = jnp.sum(C1[:, None, None, :] * h, axis=-1)  # [B,H,P]
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(-1, 1, di).astype(x.dtype)

    from .layers import rms_norm

    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, {"conv": window[:, 1:, :], "ssm": h}


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype) -> Params:
    ch = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, ch), dtype=dtype),
        "ssm": jnp.zeros(  # [B,H,P,N]: see apply_mamba_decode
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        ),
    }
