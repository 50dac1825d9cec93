"""Mixture-of-Experts FF layer: top-k router + capacity-bounded dispatch.

Dispatch is scatter/gather-based (not dense one-hot einsum) so compiled
FLOPs track *active* parameters: tokens are routed to ``[E, C, D]`` slabs
(capacity ``C = T * top_k / E * capacity_factor``), experts run as grouped
einsums, and outputs are combined with the router probabilities.  Tokens
over capacity are dropped (standard Switch-style), which the auxiliary
load-balance loss discourages.

Sharding: the expert axis ``E`` is sharded over the mesh `model` axis
(expert parallelism); the scatter/gather induce the token all-to-all.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

Params = Dict[str, Any]


def init_moe(key: jax.Array, cfg: ArchConfig) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(k1, (D, E)) * D**-0.5).astype(jnp.float32),
        "w_up": (jax.random.normal(k2, (E, D, F)) * D**-0.5).astype(dt),
        "w_gate": (jax.random.normal(k3, (E, D, F)) * D**-0.5).astype(dt),
        "w_down": (jax.random.normal(k4, (E, F, D)) * F**-0.5).astype(dt),
    }


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    cap = int(round(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, ((cap + 7) // 8) * 8)


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
    E, K = cfg.n_experts, cfg.top_k
    C = moe_capacity(cfg, T)
    xt = x.reshape(T, D)

    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [T,E]
    top_p, top_i = jax.lax.top_k(probs, K)  # [T,K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e (token fraction_e * mean prob_e).
    frac = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(frac * probs.mean(axis=0))

    # Position of each (token, slot) within its expert, row-major priority.
    flat_e = top_i.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T*K, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T*K, E]
    pos_in_e = jnp.sum(pos, axis=-1)  # [T*K]
    keep = pos_in_e < C

    # Dispatch tokens into [E, C, D] slabs (dropped tokens -> scattered to a
    # scratch row C which is sliced off).
    slot = jnp.where(keep, pos_in_e, C)
    buf = jnp.zeros((E, C + 1, D), dtype=x.dtype)
    token_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_e, slot].add(xt[token_idx])
    buf = buf[:, :C, :]  # [E,C,D]

    from ..parallel import opt_flags

    if opt_flags.get("moe_ep"):
        # §Perf: pin the dispatch slabs to expert parallelism so the
        # scatter lowers to an all-to-all instead of gathering tokens.
        from jax.sharding import PartitionSpec as P_

        buf = jax.lax.with_sharding_constraint(buf, P_("model", None, None))

    # Expert computation (grouped SwiGLU).
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # [E,C,D]
    if opt_flags.get("moe_ep"):
        from jax.sharding import PartitionSpec as P_

        out = jnp.asarray(
            jax.lax.with_sharding_constraint(out, P_("model", None, None))
        )

    # Combine: gather each kept (token, slot) expert output, weight by prob.
    out_pad = jnp.concatenate(
        [out, jnp.zeros((E, 1, D), out.dtype)], axis=1
    )  # row C = zeros for dropped tokens
    gathered = out_pad[flat_e, slot]  # [T*K, D]
    weights = (top_p.reshape(T * K) * keep).astype(gathered.dtype)
    y = jnp.zeros((T, D), dtype=gathered.dtype)
    y = y.at[token_idx].add(gathered * weights[:, None])
    return y.reshape(B, S, D), aux


# --------------------------------------------------------------------------
# §Perf iteration: shard_map local dispatch (expert-parallel, no global
# cumsum / scatter all-reduce)
# --------------------------------------------------------------------------


def apply_moe_shard_map(
    p: Params, cfg: ArchConfig, x: jnp.ndarray, mesh, batch_axes
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map.

    Tokens stay data-sharded and replicated over `model`; each model rank
    routes every local token, keeps only slots destined to its own
    ``E_loc = E/TP`` experts, computes them from a *local* capacity buffer
    (local cumsum — no cross-shard prefix sum), and the combine is one
    ``psum`` of the [T_loc, D] output over `model`.  Per-layer comm drops
    from an [E, C, D] buffer all-reduce + [T*K, E] global cumsum to a
    single activation-sized psum.
    """
    from jax.sharding import PartitionSpec as P_

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
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        frac = (
            jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
            / (T * K)
        )
        aux = E * jnp.sum(frac * probs.mean(axis=0))
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
