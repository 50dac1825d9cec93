"""Train/serve step factories (pjit-ready, donated state)."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.model import Model
from .optimizer import (
    AdamWConfig,
    AdamWState,
    adamw_init,
    adamw_update,
    compress_decompress_with_feedback,
)


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    error_feedback: Optional[Any] = None  # int8-compression residual (DCN)


def init_train_state(
    model: Model, key: jax.Array, compress_grads: bool = False
) -> TrainState:
    params = model.init(key)
    ef = None
    if compress_grads:
        from .optimizer import zeros_like_error

        ef = zeros_like_error(params)
    return TrainState(params=params, opt=adamw_init(params), error_feedback=ef)


def make_train_step(
    model: Model,
    opt_cfg: AdamWConfig,
    num_microbatches: int = 1,
    compress_grads: bool = False,
):
    """Returns train_step(state, batch) -> (state, metrics).

    ``num_microbatches > 1``: gradient accumulation via lax.scan — the
    per-microbatch backward overlaps with the previous microbatch's grad
    reduce-scatter (XLA schedules the collectives asynchronously), which is
    the standard compute/comm overlap trick at scale.
    """

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        if num_microbatches == 1:
            (loss, metrics), grads = grad_fn(state.params, batch)
        else:
            def split(x):
                B = x.shape[0]
                assert B % num_microbatches == 0
                return x.reshape(
                    (num_microbatches, B // num_microbatches) + x.shape[1:]
                )

            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                (l, m), g = grad_fn(state.params, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return acc, (l, m)

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            grads, (losses, metricses) = jax.lax.scan(body, zero, micro)
            grads = jax.tree.map(
                lambda g: g / num_microbatches, grads
            )
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, metricses)

        ef = state.error_feedback
        if compress_grads and ef is not None:
            grads, ef = compress_decompress_with_feedback(grads, ef)

        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                opt_cfg, state.params, grads, state.opt
            )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return TrainState(new_params, new_opt, ef), metrics

    return train_step


def make_serve_steps(model: Model):
    """(prefill_step, decode_step) for serving."""

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache)

    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return prefill_step, decode_step
