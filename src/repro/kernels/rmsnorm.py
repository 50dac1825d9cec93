"""Pallas TPU kernel: fused RMSNorm.

Single-pass fused normalize+scale: each grid step loads one [BR, D] row
block into VMEM, reduces the mean-square in fp32, and writes the scaled
output — one HBM read + one write per element (vs. separate
mean/rsqrt/mul HLOs).  BR is chosen per D so that the double-buffered
input and output blocks plus the fp32 temporaries fit a VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Half of v5e's 16 MiB default scoped-VMEM limit: room for Mosaic's own
# scratch next to the blocks.
VMEM_BUDGET = 8 * 2**20
MAX_BLOCK_ROWS = 256
ROW_ALIGN = 32  # sublane tile height for 8-, 16- and 32-bit dtypes


def _block_rows(d: int, itemsize: int) -> int:
    """Largest aligned row block whose pipeline fits ``VMEM_BUDGET``:
    2 input + 2 output buffers of ``itemsize`` plus two fp32 temporaries
    per element."""
    per_row = d * (4 * itemsize + 2 * 4)
    rows = VMEM_BUDGET // per_row // ROW_ALIGN * ROW_ALIGN
    return max(ROW_ALIGN, min(MAX_BLOCK_ROWS, rows))


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [BR, D]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jnp.ndarray,  # [..., D]
    scale: jnp.ndarray,  # [D]
    eps: float = 1e-6,
    *,
    interpret: bool,
) -> jnp.ndarray:
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    br = min(_block_rows(D, x.dtype.itemsize), R)
    # pad rows to a block multiple
    pad = (-R) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, scale.reshape(1, D))
    if pad:
        out = out[:R]
    return out.reshape(orig_shape)
