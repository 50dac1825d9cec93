"""Operations and bytes of the hybrid (Mamba-2 + attention + experts)
configurations' decode step, from the sizes of their configuration file.

Like ``chipbench.work`` (whose conventions hold here: a multiply-add is two
FLOPs, matrices are bf16, vectors f32), these are computed from the
configuration's sizes and never read from the program.  A period of
``attn_period`` layers holds one attention layer and Mamba-2 elsewhere;
every layer has an expert layer: a float32 router over ``n_experts``,
``experts_held`` gated experts of width ``d_ff`` and a shared gated
expert of width ``shared_d_ff``.
"""
from __future__ import annotations

from chipbench.work import BF16, F32, _conv_channels, _d_inner, _ssm_heads, conv_flops, ssd_step_flops


def _counts(cfg: dict) -> tuple[int, int]:
    """(attention layers, Mamba-2 layers)."""
    n_attn = cfg["n_layers"] // cfg["attn_period"]
    return n_attn, cfg["n_layers"] - n_attn


def mamba_matmul_params(cfg: dict) -> int:
    D, di, N, H = cfg["d_model"], _d_inner(cfg), cfg["ssm_state"], _ssm_heads(cfg)
    return D * (2 * di + 2 * N + H) + di * D


def attn_matmul_params(cfg: dict) -> int:
    D, H, G, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return 2 * D * H * K + 2 * D * G * K


def expert_params(cfg: dict) -> int:
    """One gated expert of width ``d_ff``."""
    return 3 * cfg["d_model"] * cfg["d_ff"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["d_model"] * cfg["shared_d_ff"]


def param_bytes(cfg: dict) -> int:
    """Bytes of the served weights, the held experts included; the tied
    embedding once."""
    D, Vp, E = cfg["d_model"], cfg["padded_vocab"], cfg["n_experts"]
    n_attn, n_mamba = _counts(cfg)
    di, H, ch = _d_inner(cfg), _ssm_heads(cfg), _conv_channels(cfg)
    L = cfg["n_layers"]
    mats = (n_mamba * (mamba_matmul_params(cfg) + cfg["ssm_conv"] * ch)
            + n_attn * attn_matmul_params(cfg)
            + L * (cfg["experts_held"] * expert_params(cfg) + shared_params(cfg))
            + Vp * D)
    vecs = (L * (2 * D + D * E)  # ln1, ln2, router
            + n_mamba * (3 * H + di)  # dt_bias, A_log, D, gated norm
            + D)  # final norm
    return mats * BF16 + vecs * F32 + n_mamba * ch * BF16  # conv bias


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state over the Mamba-2 layers: the f32 SSM state
    and the bf16 convolution window."""
    _, n_mamba = _counts(cfg)
    H, N, P = _ssm_heads(cfg), cfg["ssm_state"], cfg["ssm_head_dim"]
    return n_mamba * (H * N * P * F32 + (cfg["ssm_conv"] - 1) * _conv_channels(cfg) * BF16)


def decode_token_flops(cfg: dict) -> float:
    """One row of a decode step, without attention over the cache: every
    projection, the router, the shared expert, the held experts at the
    expected ``top_k * experts_held / n_experts`` picks a token, the SSD
    step and convolution, and the head."""
    n_attn, n_mamba = _counts(cfg)
    L, D = cfg["n_layers"], cfg["d_model"]
    picks = cfg["top_k"] * cfg["experts_held"] / cfg["n_experts"]
    per_layer_ff = D * cfg["n_experts"] + shared_params(cfg) + picks * expert_params(cfg)
    mats = (n_mamba * mamba_matmul_params(cfg) + n_attn * attn_matmul_params(cfg)
            + L * per_layer_ff + cfg["padded_vocab"] * D)
    return 2 * mats + n_mamba * (ssd_step_flops(cfg) + conv_flops(cfg))


def decode_step_work(cfg: dict, batch: int) -> tuple[float, int]:
    """(FLOPs, bytes) of one decode step over ``batch`` rows: every weight
    read once, each row's state read and written, each row's bf16 logits
    written.  The attention layers' reads of the key/value cache are left
    out, because a step's record carries no row lengths: at the backlog's
    lengths they are a few percent of the bytes, so the least time, and a
    share of it, is a lower bound."""
    nbytes = param_bytes(cfg) + batch * 2 * state_bytes(cfg) + batch * cfg["padded_vocab"] * BF16
    return batch * decode_token_flops(cfg), nbytes
