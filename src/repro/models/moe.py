"""Mixture-of-Experts FF layer: top-k router + dropless dispatch over the
experts this layer holds, plus an optional shared expert.

The router keeps its full width (``cfg.n_experts`` outputs) and every token
picks its top-k of all experts.  The layer holds the weights of experts
``[cfg.expert_offset, cfg.expert_offset + cfg.n_held)`` (one rank's share
under expert parallelism; all of them by default) and computes only their
part of the result: the (token, slot) assignments to held experts are
sorted by expert, gathered, run through grouped products
(``jax.lax.ragged_dot`` with the per-expert group sizes), weighted by their
gates and summed per token.  Assignments to absent experts add nothing,
and no assignment is ever dropped, so a token's output does not depend on
its batch-mates.  A shared expert (``cfg.shared_d_ff``), where the config
has one, is a gated MLP applied to every token and added.

Each phase carries a named scope inside the caller's ``moe`` scope:
``route`` (router product, top-k, aux loss), ``dispatch`` (sort and
gather), ``experts`` (grouped products), ``combine`` (gate-weighted sum)
and ``shared``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers as L

Params = Dict[str, Any]


def init_moe(key: jax.Array, cfg: ArchConfig) -> Params:
    D, F, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "router": (jax.random.normal(k1, (D, E)) * D**-0.5).astype(jnp.float32),
        "w_up": (jax.random.normal(k2, (Eh, D, F)) * D**-0.5).astype(dt),
        "w_gate": (jax.random.normal(k3, (Eh, D, F)) * D**-0.5).astype(dt),
        "w_down": (jax.random.normal(k4, (Eh, F, D)) * F**-0.5).astype(dt),
    }
    if cfg.shared_d_ff:
        p["shared"] = L.init_mlp(jax.random.fold_in(key, 4), cfg, cfg.shared_d_ff)
    return p


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    """Slots per expert of ``apply_moe_shard_map``'s capacity dispatch."""
    cap = int(round(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, ((cap + 7) // 8) * 8)


def route(p: Params, cfg: ArchConfig, xt: jnp.ndarray):
    """Top-k over all experts for tokens ``xt`` [T,D]: (gates [T,K], a
    softmax over the k largest logits; expert ids [T,K]; Switch aux loss
    over the full probabilities).  Taking the top-k of the logits rather
    than of the probabilities keeps the choice exact where the softmax
    underflows."""
    T = xt.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # [T,E]
    top_l, top_i = jax.lax.top_k(logits, K)  # [T,K]
    top_p = jax.nn.softmax(top_l, axis=-1)
    # Switch aux loss: E * sum_e (token fraction_e * mean prob_e).
    frac = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(frac * probs.mean(axis=0))
    return top_p, top_i, aux


def apply_moe(
    p: Params, cfg: ArchConfig, x: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output [B,S,D], aux load-balance loss scalar)."""
    from ..parallel import opt_flags

    if opt_flags.get("moe_a2a") and opt_flags.get("mesh") is not None:
        return apply_moe_shard_map(
            p, cfg, x, opt_flags.get("mesh"), opt_flags.get("batch_axes")
        )
    B, S, D = x.shape
    T = B * S
    K, Eh = cfg.top_k, cfg.n_held
    xt = x.reshape(T, D)

    with jax.named_scope("route"):
        top_p, top_i, aux = route(p, cfg, xt)

    with jax.named_scope("dispatch"):
        # Held experts' assignments first, grouped by expert; the rest
        # (group Eh) last, outside every group.
        local = top_i.reshape(T * K) - cfg.expert_offset
        held = (local >= 0) & (local < Eh)
        group = jnp.where(held, local, Eh)
        order = jnp.argsort(group, stable=True)  # [T*K] flat (token, slot)
        sizes = jnp.zeros((Eh + 1,), jnp.int32).at[group].add(1)[:Eh]
        xs = xt[order // K]  # [T*K,D]

    with jax.named_scope("experts"):
        up = jax.lax.ragged_dot(xs, p["w_up"], sizes)
        gate = jax.lax.ragged_dot(xs, p["w_gate"], sizes)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["w_down"], sizes)

    with jax.named_scope("combine"):
        # Rows past the held groups are left undefined by the grouped
        # product: select, do not multiply, them away.
        w = (top_p.reshape(T * K) * held)[order]
        out = jnp.where(held[order][:, None], out.astype(jnp.float32) * w[:, None], 0.0)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * K, dtype=order.dtype))
        y = out[inv].reshape(T, K, D).sum(axis=1)

    if "shared" in p:
        with jax.named_scope("shared"):
            y = y + L.apply_mlp(p["shared"], cfg, x).reshape(T, D).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, D), aux


# --------------------------------------------------------------------------
# §Perf iteration: shard_map local dispatch (expert-parallel, no global
# cumsum / scatter all-reduce)
# --------------------------------------------------------------------------


def apply_moe_shard_map(
    p: Params, cfg: ArchConfig, x: jnp.ndarray, mesh, batch_axes
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map (opt flag ``moe_a2a`` only).

    It keeps the capacity-bounded (Switch-style) dispatch, which drops
    assignments past ``moe_capacity`` slots an expert, and it covers only
    configs that hold every expert and have no shared expert.

    Tokens stay data-sharded and replicated over `model`; each model rank
    routes every local token, keeps only slots destined to its own
    ``E_loc = E/TP`` experts, computes them from a *local* capacity buffer
    (local cumsum — no cross-shard prefix sum), and the combine is one
    ``psum`` of the [T_loc, D] output over `model`.  Per-layer comm drops
    from an [E, C, D] buffer all-reduce + [T*K, E] global cumsum to a
    single activation-sized psum.
    """
    from jax.sharding import PartitionSpec as P_

    if cfg.n_held != cfg.n_experts or cfg.shared_d_ff:
        raise NotImplementedError(
            "apply_moe_shard_map needs every expert held and no shared expert"
        )
    E, K, D = cfg.n_experts, cfg.top_k, cfg.d_model
    model_size = mesh.shape["model"]
    assert E % model_size == 0
    E_loc = E // model_size
    b_spec = P_(batch_axes, None, None)

    def local_moe(xb, router, w_up, w_gate, w_down):
        B_loc, S, _ = xb.shape
        T = B_loc * S
        C = moe_capacity(cfg, T)
        xt = xb.reshape(T, D)
        top_p, top_i, aux = route({"router": router}, cfg, xt)
        aux = jax.lax.pmean(aux, "model")

        rank = jax.lax.axis_index("model")
        flat_e = top_i.reshape(T * K)
        local_e = flat_e - rank * E_loc
        mine = (local_e >= 0) & (local_e < E_loc)
        le = jnp.where(mine, local_e, 0)
        onehot = jax.nn.one_hot(le, E_loc, dtype=jnp.int32) * mine[:, None]
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
        pos_in_e = jnp.sum(pos, axis=-1)
        keep = mine & (pos_in_e < C)
        slot = jnp.where(keep, pos_in_e, C)
        token_idx = jnp.repeat(jnp.arange(T), K)
        buf = jnp.zeros((E_loc, C + 1, D), dtype=xb.dtype)
        buf = buf.at[le, slot].add(xt[token_idx] * keep[:, None].astype(xb.dtype))
        buf = buf[:, :C, :]

        up = jnp.einsum("ecd,edf->ecf", buf, w_up)
        gate = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        hh = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", hh, w_down)
        out_pad = jnp.concatenate(
            [out, jnp.zeros((E_loc, 1, D), out.dtype)], axis=1
        )
        gathered = out_pad[le, slot]
        w = (top_p.reshape(T * K) * keep).astype(gathered.dtype)
        y = jnp.zeros((T, D), dtype=gathered.dtype)
        y = y.at[token_idx].add(gathered * w[:, None])
        y = jax.lax.psum(y, "model")
        return y.reshape(B_loc, S, D), aux

    y, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            b_spec,
            P_(None, None),
            P_("model", None, None),
            P_("model", None, None),
            P_("model", None, None),
        ),
        out_specs=(b_spec, P_()),
        check_vma=False,
    )(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])
    return y, aux
