"""Jit'd public wrappers around the Pallas kernels.

* ``flash_attention`` — custom_vjp: Pallas forward, recompute-based
  pure-jnp backward (flash-style: no S x T residuals saved).
* ``ssd_scan`` — chunk-padded wrapper around the SSD Pallas kernel.
* ``rmsnorm`` — fused norm wrapper.

The kernels run compiled on a TPU backend and interpreted on the CPU
backend (tests); any other backend raises.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .rmsnorm import rmsnorm_pallas
from .ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"the default backend is {backend!r}"
        )
    return backend == "cpu"


# --------------------------------------------------------------------------
# flash attention (custom vjp: pallas fwd, recompute bwd)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    return flash_attention_pallas(
        q, k, v, q_pos, kv_pos, causal=causal, window=window,
        interpret=_interpret(),
    )


def _fa_fwd(q, k, v, q_pos, kv_pos, causal, window):
    out = flash_attention(q, k, v, q_pos, kv_pos, causal, window)
    return out, (q, k, v, q_pos, kv_pos)


def _fa_bwd(causal, window, res, g):
    q, k, v, q_pos, kv_pos = res
    # Recompute-based backward through the reference (flash-style: no
    # S x T tensor was saved by the forward).
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, q_pos, kv_pos, causal=causal, window=window
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    S = x.shape[1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, state = ssd_scan_pallas(
        x, dt, A, Bm, Cm, chunk=chunk, interpret=_interpret()
    )
    return y[:, :S], state


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    return rmsnorm_pallas(x, scale, eps=eps, interpret=_interpret())
