"""Sharding rules: parameter/activation PartitionSpecs per architecture.

Mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model') multi-pod.

Strategy (baseline; §Perf iterates):
* 2-D param sharding — tensor-parallel dims (heads, ff, experts, vocab) on
  `model`; the other large dim on `data` (FSDP/ZeRO-3 style). XLA inserts
  the all-gathers for FSDP params and reduce-scatters for grads.
* activations: batch on ('pod', 'data') when divisible; attention heads /
  expert dim on `model`.
* KV caches: batch on ('pod','data') when divisible, else sequence on
  'data'; kv-head dim on `model` only when divisible (MQA replicates kv).

Every rule degrades to replication when a dim isn't divisible — so every
(arch x shape x mesh) cell lowers, and the dry-run exposes the cost.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _fits(mesh: Mesh, dim: int, *axes: str) -> bool:
    size = 1
    for a in axes:
        size *= axis_size(mesh, a)
    return size > 1 and dim % size == 0


def maybe(mesh: Mesh, dim: int, *axes: str):
    """Return the axis (tuple) if the dim divides, else None (replicate)."""
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    if _fits(mesh, dim, *axes):
        return axes if len(axes) > 1 else axes[0]
    # try a prefix (e.g. ('pod','data') -> ('data',))
    for i in range(len(axes) - 1, 0, -1):
        if _fits(mesh, dim, *axes[i:]):
            sub = axes[i:]
            return sub if len(sub) > 1 else sub[0]
    return None


def batch_axes(mesh: Mesh, batch: int):
    return maybe(mesh, batch, "pod", "data")


# --------------------------------------------------------------------------
# parameter sharding
# --------------------------------------------------------------------------


def _param_spec(path: Tuple[str, ...], leaf, cfg: ArchConfig, mesh: Mesh) -> P:
    """PartitionSpec for one parameter; `path` is the key path (strings)."""
    name = path[-1]
    scanned = "blocks" in path  # leading n_blocks axis
    shape = leaf.shape[1:] if scanned else leaf.shape

    def spec(*axes) -> P:
        return P(*( (None,) + axes if scanned else axes ))

    if name == "tokens":  # [V, D]
        s = spec(maybe(mesh, shape[0], "model"), maybe(mesh, shape[1], "data"))
    elif name == "unembed":  # [D, V]
        s = spec(maybe(mesh, shape[0], "data"), maybe(mesh, shape[1], "model"))
    elif name == "wq":  # [D, H, K]
        s = spec(maybe(mesh, shape[0], "data"), maybe(mesh, shape[1], "model"), None)
    elif name in ("wk", "wv"):  # [D, G, K] — G may be < model size (MQA)
        s = spec(maybe(mesh, shape[0], "data"), maybe(mesh, shape[1], "model"), None)
    elif name == "wo":  # [H, K, D]
        s = spec(maybe(mesh, shape[0], "model"), None, maybe(mesh, shape[2], "data"))
    elif name in ("w_up", "w_gate", "w_down") and len(shape) == 3:
        # MoE experts [E, D, F] / [E, F, D]: expert parallel on `model`.
        s = spec(maybe(mesh, shape[0], "model"), maybe(mesh, shape[1], "data"), None)
    elif name in ("w_up", "w_gate"):  # [D, F]
        s = spec(maybe(mesh, shape[0], "data"), maybe(mesh, shape[1], "model"))
    elif name == "w_down":  # [F, D]
        s = spec(maybe(mesh, shape[0], "model"), maybe(mesh, shape[1], "data"))
    elif name == "router":  # [D, E]
        s = spec(maybe(mesh, shape[0], "data"), None)
    elif name == "in_proj":  # mamba [D, Proj]
        s = spec(maybe(mesh, shape[0], "data"), maybe(mesh, shape[1], "model"))
    elif name == "out_proj":  # mamba [d_inner, D]
        s = spec(maybe(mesh, shape[0], "model"), maybe(mesh, shape[1], "data"))
    elif name in ("w1", "w2", "frontend_proj"):  # frontend projections
        s = spec(None, maybe(mesh, shape[1], "data"))
    elif leaf.ndim - (1 if scanned else 0) <= 1:
        s = spec(*(None,) * len(shape))  # norms, biases, A_log, ... replicate
    else:
        s = spec(*(None,) * len(shape))
    return s


def param_shardings(cfg: ArchConfig, params_tree: Any, mesh: Mesh):
    """NamedShardings matching the (possibly abstract) params pytree."""

    def one(path, leaf):
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        return NamedSharding(mesh, _param_spec(keys, leaf, cfg, mesh))

    return jax.tree_util.tree_map_with_path(one, params_tree)


# --------------------------------------------------------------------------
# activation / batch / cache sharding
# --------------------------------------------------------------------------


def batch_shardings(cfg: ArchConfig, batch_specs: Any, mesh: Mesh):
    """Input batch: shard the leading batch dim over ('pod','data')."""

    def one(leaf):
        b = batch_axes(mesh, leaf.shape[0])
        rest = (None,) * (len(leaf.shape) - 1)
        return NamedSharding(mesh, P(b, *rest))

    return jax.tree.map(one, batch_specs)


def cache_shardings(cfg: ArchConfig, cache_tree: Any, mesh: Mesh):
    """KV/SSM cache sharding (leaves have leading n_blocks axis).

    attn k/v [n, B, W, G, K]: batch over ('pod','data') if divisible else
    W over 'data'; G over 'model' if divisible.
    mamba ssm [n, B, H, P, N]: batch over ('pod','data') else H on 'model'.
    """

    def one(path, leaf):
        keys = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        name = keys[-1]
        shape = leaf.shape[1:]  # strip n_blocks
        if name in ("k", "v"):
            b = batch_axes(mesh, shape[0])
            g = maybe(mesh, shape[2], "model")
            w = None if b is not None else maybe(mesh, shape[1], "data")
            return NamedSharding(mesh, P(None, b, w, g, None))
        if name == "pos":  # [n, 1, W]
            return NamedSharding(mesh, P(None, None, None))
        if name == "ssm":  # [n, B, H, P, N]
            b = batch_axes(mesh, shape[0])
            h = maybe(mesh, shape[1], "model")
            return NamedSharding(mesh, P(None, b, h, None, None))
        if name == "conv":  # [n, B, k-1, Ch]
            b = batch_axes(mesh, shape[0])
            ch = maybe(mesh, shape[2], "model")
            return NamedSharding(mesh, P(None, b, None, ch))
        raise ValueError(f"unknown cache leaf {keys}")

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
