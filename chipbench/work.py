"""Operations and bytes that the benchmark's cells need, from their shapes.

These are the yardstick for every utilisation and roofline share the
benchmark reports, so they are computed from the configuration's sizes and
never read from the program or from the compiler.  FLOPs count a
multiply-add as two.  "Model" FLOPs count what the forward and backward
passes require; recomputation (remat) is never counted.

``cfg`` is the ``config`` object of a configuration file under
``chipbench/configs``: a dict of the sizes the cell runs at.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def padded_vocab(cfg: dict) -> int:
    return cfg["padded_vocab"]


def _d_inner(cfg: dict) -> int:
    return cfg["ssm_expand"] * cfg["d_model"]


def _ssm_heads(cfg: dict) -> int:
    return _d_inner(cfg) // cfg["ssm_head_dim"]


def _conv_channels(cfg: dict) -> int:
    return _d_inner(cfg) + 2 * cfg["ssm_state"]


# ---------------------------------------------------------------- params


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that enter a matrix multiplication."""
    D = cfg["d_model"]
    if cfg["family"] == "ssm":
        di, N, H = _d_inner(cfg), cfg["ssm_state"], _ssm_heads(cfg)
        return D * (2 * di + 2 * N + H) + di * D
    H, G, K, F = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"]
    attn = D * H * K + 2 * D * G * K + H * K * D
    mlp = (3 if cfg["mlp_gated"] else 2) * D * F
    return attn + mlp


def head_params(cfg: dict) -> int:
    return padded_vocab(cfg) * cfg["d_model"]


def matmul_params(cfg: dict) -> int:
    """All weights that a token's forward pass multiplies: the layers and
    the output head (the embedding lookup is a gather, not a product)."""
    return cfg["n_layers"] * layer_matmul_params(cfg) + head_params(cfg)


def param_bytes(cfg: dict) -> int:
    """Bytes of the served weights: matrices in bf16, vectors in f32, as
    the configuration's ``dtype`` of bfloat16 stores them."""
    D, L, Vp = cfg["d_model"], cfg["n_layers"], padded_vocab(cfg)
    mats = L * layer_matmul_params(cfg) + Vp * D
    if not cfg["tie_embeddings"]:
        mats += Vp * D
    vecs = D  # final norm
    if cfg["family"] == "ssm":
        di, H, ch = _d_inner(cfg), _ssm_heads(cfg), _conv_channels(cfg)
        mats += L * cfg["ssm_conv"] * ch
        vecs += L * (D + 3 * H + di)  # ln1, dt_bias/A_log/D, gated norm
        return mats * BF16 + vecs * F32 + L * ch * BF16  # conv bias
    vecs += L * 2 * D  # ln1, ln2
    return mats * BF16 + vecs * F32


# ---------------------------------------------------------------- mixers


def ssd_step_flops(cfg: dict) -> int:
    """One token of the SSD recurrence, per layer:
    h = exp(dt A) h + dt B x^T  and  y = C h + D x."""
    H, N, P = _ssm_heads(cfg), cfg["ssm_state"], cfg["ssm_head_dim"]
    return 4 * H * N * P + 2 * H * P


def conv_flops(cfg: dict) -> int:
    """One token of the depthwise causal convolution, per layer."""
    return 2 * cfg["ssm_conv"] * _conv_channels(cfg)


def attention_ctx_sum(seq: int, window: int | None) -> int:
    """Sum over the ``seq`` causal queries of the keys each one sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_work(
    batch: int, seq: int, n_heads: int, n_kv_heads: int, head_dim: int,
    window: int | None = None, dtype_bytes: int = BF16,
) -> tuple[int, int]:
    """(FLOPs, bytes) of causal attention over its own ``seq`` keys:
    QK^T and PV over the keys each query may see; bytes read q, k, v and
    write the output once."""
    flops = 4 * batch * n_heads * head_dim * attention_ctx_sum(seq, window)
    elems = batch * seq * (2 * n_heads + 2 * n_kv_heads) * head_dim
    return flops, elems * dtype_bytes


def ssd_scan_work(
    batch: int, seq: int, n_heads: int, head_dim: int, state: int,
    chunk: int, dtype_bytes: int = F32,
) -> tuple[int, int]:
    """(FLOPs, bytes) of the chunked SSD scan (Dao & Gu 2024) at chunk
    ``chunk``: C B^T and its product with x inside each chunk, each chunk's
    state, and each position's read of the incoming state.  Bytes read x,
    dt, B, C and write y and the final state once."""
    B, S, H, P, N, Q = batch, seq, n_heads, head_dim, state, chunk
    nc = -(-S // Q)
    intra = 2 * B * nc * Q * Q * N + 2 * B * nc * Q * Q * H * P
    states = 2 * B * nc * Q * H * N * P
    y_off = 2 * B * nc * Q * H * N * P
    read = B * S * (H * P + H + 2 * N)
    write = B * S * H * P + B * H * N * P
    return intra + states + y_off, (read + write) * dtype_bytes


def rmsnorm_work(
    rows: int, dim: int, in_bytes: int = BF16, out_bytes: int = BF16
) -> tuple[int, int]:
    """(FLOPs, bytes): square, sum, scale by the inverse root and by the
    weight, per element; bytes read the rows and the f32 weight and write
    the rows."""
    return 4 * rows * dim, rows * dim * (in_bytes + out_bytes) + dim * F32


# ---------------------------------------------------------------- steps


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """Model FLOPs of one trained token: three times the forward (forward,
    and a backward of twice its cost), with no recomputation."""
    fwd = 2 * matmul_params(cfg)
    L = cfg["n_layers"]
    if cfg["family"] == "ssm":
        fwd += L * (ssd_step_flops(cfg) + conv_flops(cfg))
    else:
        att, _ = attention_work(
            1, seq, cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            cfg.get("sliding_window"),
        )
        fwd += L * att // seq
    return 3 * fwd


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one sequence's recurrent state over all layers: the f32
    SSM state and the bf16 convolution window."""
    H, N, P = _ssm_heads(cfg), cfg["ssm_state"], cfg["ssm_head_dim"]
    conv = (cfg["ssm_conv"] - 1) * _conv_channels(cfg) * BF16
    return cfg["n_layers"] * (H * N * P * F32 + conv)


def decode_step_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one SSM decode step over ``batch`` rows: every
    weight read once, each row's state read and written, each row's bf16
    logits written."""
    if cfg["family"] != "ssm":
        raise ValueError("decode_step_work covers the SSM decode step")
    L = cfg["n_layers"]
    per_row = 2 * matmul_params(cfg) + L * (ssd_step_flops(cfg) + conv_flops(cfg))
    nbytes = (
        param_bytes(cfg)
        + batch * 2 * ssm_state_bytes(cfg)
        + batch * padded_vocab(cfg) * BF16
    )
    return batch * per_row, nbytes


def prefill_work(cfg: dict, seq: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one SSM prefill of ``seq`` tokens: the layers over
    every token, the head over the last one; weights read once, the state
    written once."""
    if cfg["family"] != "ssm":
        raise ValueError("prefill_work covers the SSM prefill")
    L = cfg["n_layers"]
    layers = 2 * L * layer_matmul_params(cfg) + L * (
        ssd_step_flops(cfg) + conv_flops(cfg)
    )
    flops = seq * layers + 2 * head_params(cfg)
    nbytes = param_bytes(cfg) + ssm_state_bytes(cfg) + padded_vocab(cfg) * BF16
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    """The roofline: the least time the chip could take for the work."""
    return max(flops / peak_flops, nbytes / peak_bw)
