"""Pieces shared by the plain references in ``chipbench/configs/*.py``.

Everything here is straightforward ``jax.numpy`` in float32 at the highest
matmul precision, written from the published descriptions and importing
nothing of the program.  ``precision`` selects the arithmetic: ``"f32"``
for the reference, ``"fp8"`` for the control, which holds what the
configuration holds in bfloat16 in float8 e4m3 instead (the nearest
precision below): both operands and the result of every product, and the
residual stream between layers, are rounded to it; products accumulate
in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("f32", "fp8")


def rounder(precision: str):
    if precision == "f32":
        return lambda x: x
    if precision == "fp8":
        return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")


def mm(spec: str, a, b, precision: str):
    """einsum of two operands in float32 at HIGHEST; the operands and the
    product are rounded to ``precision``, as a program holding its weights
    and activations in it would hold them."""
    q = rounder(precision)
    return q(jnp.einsum(
        spec, q(a.astype(jnp.float32)), q(b.astype(jnp.float32)),
        precision=jax.lax.Precision.HIGHEST,
    ))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def nll_sum(logits, labels):
    """Sum over positions with a label >= 0 of -log softmax(logits)[label],
    over the whole (padded) head, as the configuration runs it."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, lse - gold, 0.0))


def normal_params(key, shapes: dict, dtype) -> dict:
    """One normal draw per named leaf: {path: (shape, scale)}, in a fixed
    order of the sorted paths."""
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    return {
        n: (jax.random.normal(k, shapes[n][0], jnp.float32) * shapes[n][1]).astype(dtype)
        for n, k in zip(names, keys)
    }


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


# ---------------------------------------------------------------- AdamW


def cosine_lr(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr_peak`` over ``warmup_steps``, then a cosine
    decay to 0 at ``total_steps``."""
    if step < opt["warmup_steps"]:
        return opt["lr_peak"] * step / max(opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return 0.5 * opt["lr_peak"] * (1.0 + math.cos(math.pi * prog))


@jax.jit
def _adamw_leaf(p, g, m, v, lr, scale, b1, b2, eps, wd, b1c, b2c, decay):
    g = g * scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    delta = (m / b1c) / (jnp.sqrt(v / b2c) + eps) + decay * wd * p
    return p - lr * delta, m, v


class AdamW:
    """AdamW (Loshchilov & Hutter) with global-norm clipping, in float32.
    Weight decay applies to every leaf stored with rank 2 or more, as the
    configurations state: per-layer vectors stacked over the layers are
    decayed too.  The moments live on the host, so
    that a reference of a large model fits the chip beside its weights and
    gradients; each leaf's update runs on the device."""

    def __init__(self, opt: dict, params: dict):
        self.opt = opt
        self.step = 0
        self.m = {k: np.zeros(p.shape, np.float32) for k, p in params.items()}
        self.v = {k: np.zeros(p.shape, np.float32) for k, p in params.items()}

    def update(self, params: dict, grads: dict) -> tuple[dict, dict]:
        """Returns (new params, the clipped gradient's norm per leaf)."""
        o = self.opt
        gnorm = math.sqrt(sum(float(jnp.sum(g * g)) for g in grads.values()))
        scale = min(1.0, o["clip_norm"] / (gnorm + 1e-9))
        self.step += 1
        lr = cosine_lr(o, self.step)
        b1c, b2c = 1.0 - o["b1"] ** self.step, 1.0 - o["b2"] ** self.step
        new, gn = {}, {}
        for k in sorted(params):
            p, g = params.pop(k), grads.pop(k)
            gn[k] = float(jnp.linalg.norm(g.ravel())) * scale
            p, m, v = _adamw_leaf(
                p, g, jnp.asarray(self.m[k]), jnp.asarray(self.v[k]),
                lr, scale, o["b1"], o["b2"], o["eps"], o["weight_decay"],
                b1c, b2c, float(p.ndim >= 2),
            )
            self.m[k], self.v[k] = np.asarray(m), np.asarray(v)
            new[k] = p
        return new, gn


# ---------------------------------------------------------------- pytrees


def flatten(tree) -> dict:
    """{"a/b/c": leaf} from a nested dict."""
    out = {}

    def walk(prefix, t):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(f"{prefix}{k}/", v)
        else:
            out[prefix[:-1]] = t

    walk("", tree)
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out
