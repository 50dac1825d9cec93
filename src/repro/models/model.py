"""Model facade: init / loss / prefill / decode for all six families.

Layers are stacked with ``jax.lax.scan`` over *scan blocks* (hybrid archs
scan over super-blocks of ``attn_period`` sub-layers), so full-size configs
(up to 398 B params) lower and compile quickly.  Per-block activation
rematerialization (``jax.checkpoint``) bounds training memory.

Each layer's ops carry a ``jax.named_scope`` (``embed``, ``norm``, ``attn``
or ``mamba``, ``mlp`` or ``moe``, ``head``; inside ``moe`` the expert
layer's ``route``, ``dispatch``, ``experts``, ``combine`` and ``shared``)
in their HLO ``op_name`` metadata, through remat and the backward pass,
so a device trace can be split by layer.  Scopes are metadata only: the compiled code is unchanged,
and JAX's persistent compile cache leaves metadata out of its key, so a
program loaded from a cache filled before the scopes carries none.

Batch dicts per family (see ``input_specs`` in launch/dryrun.py):
  dense/moe/ssm/hybrid : {"tokens": [B,S] i32, "labels": [B,S] i32}
  vlm   : {"tokens": [B,S_text], "labels": [B,S_text],
           "patch_embeds": [B,T_img,frontend_dim]}   (S_text+T_img = S)
  audio : {"frames": [B,S,frontend_dim], "labels": [B,S]}
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers as L
from . import mamba as M
from . import moe as X

Params = Dict[str, Any]


def _init_sub(key: jax.Array, cfg: ArchConfig, mixer: str, ff: str) -> Params:
    ks = jax.random.split(key, 3)
    sub: Params = {"ln1": jnp.ones((cfg.d_model,), jnp.float32)}
    if mixer == "attn":
        sub["attn"] = L.init_attention(ks[0], cfg)
    else:
        sub["mamba"] = M.init_mamba(ks[0], cfg)
    if ff == "dense":
        sub["ln2"] = jnp.ones((cfg.d_model,), jnp.float32)
        sub["mlp"] = L.init_mlp(ks[1], cfg)
    elif ff == "moe":
        sub["ln2"] = jnp.ones((cfg.d_model,), jnp.float32)
        sub["moe"] = X.init_moe(ks[1], cfg)
    return sub


def _constrain_sub(h: jnp.ndarray) -> jnp.ndarray:
    """Per-sublayer residual constraint (§Perf iteration 3): re-sharding the
    residual stream after *every* sublayer keeps the TP psum at
    reduce-scatter volume instead of full all-reduce (Megatron-SP)."""
    from ..parallel import opt_flags

    if opt_flags.get("sp_sub") and h.ndim == 3 and h.shape[1] > 1:
        from jax.sharding import PartitionSpec as P_

        b = opt_flags.get("batch_axes")
        h = jax.lax.with_sharding_constraint(h, P_(b, "model", None))
    return h


def _apply_sub(
    sub: Params,
    cfg: ArchConfig,
    mixer: str,
    ff: str,
    h: jnp.ndarray,
    q_pos: jnp.ndarray,
    cache: Optional[Params],
    cache_index: Optional[jnp.ndarray],
    self_attend: bool,
    decode: bool,
) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("norm"):
        y = L.rms_norm(h, sub["ln1"], cfg.norm_eps)
    with jax.named_scope(mixer):
        if mixer == "attn":
            y, new_cache = L.apply_attention(
                sub["attn"], cfg, y, q_pos,
                cache=cache, cache_index=cache_index, self_attend=self_attend,
            )
        elif decode:
            y, new_cache = M.apply_mamba_decode(sub["mamba"], cfg, y, cache)
        else:
            y, new_cache = M.apply_mamba(
                sub["mamba"], cfg, y, return_cache=cache is not None
            )
    h = _constrain_sub(h + _residual(cfg, y))
    if ff != "none":
        with jax.named_scope("norm"):
            y = L.rms_norm(h, sub["ln2"], cfg.norm_eps)
        if ff == "dense":
            with jax.named_scope("mlp"):
                y = L.apply_mlp(sub["mlp"], cfg, y)
        else:
            with jax.named_scope("moe"):
                y, aux = X.apply_moe(sub["moe"], cfg, y)
        h = _constrain_sub(h + _residual(cfg, y))
    return h, new_cache, aux


def _residual(cfg: ArchConfig, y: jnp.ndarray) -> jnp.ndarray:
    """A branch's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return y


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.kinds = cfg.layer_kinds()
        self.n_blocks = cfg.n_scan_blocks
        # Optional PartitionSpec applied to the [B, S, D] residual stream at
        # block boundaries (Megatron-style sequence parallelism): sharding S
        # over the tensor-parallel axis cuts per-device activation traffic
        # by the TP degree.  Set by launch/dryrun.py --opt sp (see §Perf).
        self.act_spec = None

    def _constrain(self, h: jnp.ndarray) -> jnp.ndarray:
        if self.act_spec is not None and h.ndim == 3 and h.shape[1] > 1:
            h = jax.lax.with_sharding_constraint(h, self.act_spec)
        return h

    # ---- init ----------------------------------------------------------

    def _init_block(self, key: jax.Array) -> Params:
        ks = jax.random.split(key, len(self.kinds))
        return {
            f"sub{i}": _init_sub(ks[i], self.cfg, mixer, ff)
            for i, (mixer, ff) in enumerate(self.kinds)
        }

    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        k_embed, k_blocks, k_front = jax.random.split(key, 3)
        block_keys = jax.random.split(k_blocks, self.n_blocks)
        params: Params = {
            "embed": L.init_embedding(k_embed, cfg),
            "blocks": jax.vmap(self._init_block)(block_keys),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        dt = L.dtype_of(cfg)
        if cfg.family == "vlm":
            kf1, kf2 = jax.random.split(k_front)
            F, D = cfg.frontend_dim, cfg.d_model
            params["projector"] = {
                "w1": (jax.random.normal(kf1, (F, D)) * F**-0.5).astype(dt),
                "w2": (jax.random.normal(kf2, (D, D)) * D**-0.5).astype(dt),
            }
        elif cfg.family == "audio":
            F, D = cfg.frontend_dim, cfg.d_model
            params["frontend_proj"] = (
                jax.random.normal(k_front, (F, D)) * F**-0.5
            ).astype(dt)
        return params

    def param_specs(self, key: jax.Array | None = None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)

    # ---- backbone -------------------------------------------------------

    def _backbone(
        self,
        params: Params,
        h: jnp.ndarray,
        q_pos: jnp.ndarray,
        cache: Optional[Params] = None,
        cache_index: Optional[jnp.ndarray] = None,
        self_attend: bool = True,
        decode: bool = False,
        remat: bool = False,
    ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
        cfg, kinds = self.cfg, self.kinds

        def block_fn(h, block_params, block_cache):
            new_cache = {} if block_cache is not None else None
            aux_total = jnp.zeros((), jnp.float32)
            for i, (mixer, ff) in enumerate(kinds):
                sub_cache = block_cache[f"sub{i}"] if block_cache else None
                h, nc, aux = _apply_sub(
                    block_params[f"sub{i}"], cfg, mixer, ff, h, q_pos,
                    sub_cache, cache_index, self_attend, decode,
                )
                aux_total = aux_total + aux
                if new_cache is not None:
                    new_cache[f"sub{i}"] = nc
            return h, new_cache, aux_total

        if remat:
            block_fn = jax.checkpoint(
                block_fn, policy=jax.checkpoint_policies.nothing_saveable
            )

        h = self._constrain(h)
        if cache is None:
            def body(carry, block_params):
                h, nc, aux = block_fn(carry, block_params, None)
                return self._constrain(h), aux
            h, auxs = jax.lax.scan(body, h, params["blocks"])
            return h, None, jnp.sum(auxs)

        def body(carry, xs):
            block_params, block_cache = xs
            h, new_cache, aux = block_fn(carry, block_params, block_cache)
            return self._constrain(h), (new_cache, aux)

        h, (new_cache, auxs) = jax.lax.scan(
            body, h, (params["blocks"], cache)
        )
        return h, new_cache, jnp.sum(auxs)

    # ---- family-specific embedding --------------------------------------

    def _embed_inputs(
        self, params: Params, batch: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, int]:
        """Returns (h [B,S,D], n_prefix) where n_prefix = non-text prefix."""
        cfg = self.cfg
        if cfg.family == "audio":
            h = jnp.einsum(
                "bsf,fd->bsd",
                batch["frames"].astype(L.dtype_of(cfg)),
                params["frontend_proj"],
            )
            return h, 0
        tok = L.embed_tokens(params["embed"], cfg, batch["tokens"])
        if cfg.family == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(L.dtype_of(cfg))
            proj = params["projector"]
            img = jnp.einsum("btf,fd->btd", pe, proj["w1"])
            img = jnp.einsum("btd,de->bte", jax.nn.gelu(img), proj["w2"])
            h = jnp.concatenate([img, tok], axis=1)
            return h, img.shape[1]
        return tok, 0

    # ---- public API -------------------------------------------------------

    def loss(
        self, params: Params, batch: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        with jax.named_scope("embed"):
            h, n_prefix = self._embed_inputs(params, batch)
        S = h.shape[1]
        q_pos = jnp.arange(S, dtype=jnp.int32)
        h, _, aux = self._backbone(params, h, q_pos, remat=True)
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
            if n_prefix:
                h = h[:, n_prefix:, :]
            logits = L.unembed(params["embed"], cfg, h)
            xent, n_tok = L.cross_entropy(logits, batch["labels"])
        loss = xent + 0.01 * aux
        return loss, {"xent": xent, "aux": aux, "n_tokens": n_tok}

    def init_cache(
        self, batch: int, max_len: int, dtype=None
    ) -> Params:
        cfg = self.cfg
        dtype = dtype or L.dtype_of(cfg)

        def block_cache() -> Params:
            out: Params = {}
            for i, (mixer, _ff) in enumerate(self.kinds):
                if mixer == "attn":
                    out[f"sub{i}"] = L.init_attn_cache(cfg, batch, max_len, dtype)
                else:
                    out[f"sub{i}"] = M.init_mamba_cache(cfg, batch, dtype)
            return out

        one = block_cache()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self.n_blocks,) + a.shape),
            one,
        )

    def prefill(
        self,
        params: Params,
        batch: Dict[str, jnp.ndarray],
        cache: Optional[Params] = None,
    ) -> Tuple[jnp.ndarray, Optional[Params]]:
        """Process the prompt; returns (last-token logits, filled cache)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            h, _ = self._embed_inputs(params, batch)
        S = h.shape[1]
        q_pos = jnp.arange(S, dtype=jnp.int32)
        h, new_cache, _ = self._backbone(
            params, h, q_pos,
            cache=cache,
            cache_index=jnp.zeros((), jnp.int32),
            self_attend=True,
        )
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
            logits = L.unembed(params["embed"], cfg, h[:, -1:, :])
        return logits, new_cache

    def decode_step(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,  # [B,1]
        pos: jnp.ndarray,  # scalar i32 (shared) or [B] i32 (per-row)
    ) -> Tuple[jnp.ndarray, Params]:
        """One decode step.  ``pos`` is the absolute position of this
        token: a scalar when the whole batch decodes in lockstep, or a
        per-row ``[B]`` vector when rows sit at different depths (the
        batched serving engine's continuous-refill loop)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            h = L.embed_tokens(params["embed"], cfg, tokens)
        pos = pos.astype(jnp.int32)
        q_pos = pos[None] if pos.ndim == 0 else pos[:, None]
        h, new_cache, _ = self._backbone(
            params, h, q_pos,
            cache=cache, cache_index=pos,
            self_attend=False, decode=True,
        )
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
            logits = L.unembed(params["embed"], cfg, h)
        return logits, new_cache


def n_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_params(cfg: ArchConfig, params: Params) -> int:
    """Active (per-token) params: total minus inactive expert fraction."""
    total = n_params(params)
    if cfg.n_experts == 0:
        return total
    expert = 0
    blocks = params["blocks"]
    for i, (_mixer, ff) in enumerate(cfg.layer_kinds()):
        if ff == "moe":
            moe_p = blocks[f"sub{i}"]["moe"]
            expert += sum(
                moe_p[k].size for k in ("w_up", "w_gate", "w_down")
            )
    inactive = expert * (1.0 - cfg.top_k / cfg.n_experts)
    return int(total - inactive)
