"""Plain reference of Mamba-2 (arXiv:2405.21060) at the sizes of
``mamba2-370m.json``, and the weights the benchmark serves it with.

Float32 throughout at HIGHEST matmul precision (``precision="fp8"``, the
control, rounds the operands and result of every product and the
residual stream).  Each layer: RMSNorm, the
input projection to (z, x, B, C, dt), a depthwise causal convolution over
(x, B, C) with SiLU, the SSD recurrence h_t = exp(dt_t A) h_{t-1} +
dt_t B_t x_t^T, y_t = C_t h_t + D x_t (computed by the paper's chunked
"SSD minimal" algorithm, exact in exact arithmetic), a gated RMSNorm
norm(y * silu(z)) and the output projection, added to the residual.  The
head is tied to the embedding.  Departures, as the configuration states:
RMSNorm eps 1e-6; the head and its softmax cover the padded 50432 rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mm, nll_sum, normal_params, rms_norm, rounder

CHUNK = 256  # the reference's own SSD chunk (less for shorter sequences); any chunk gives the same y


def _sizes(c):
    di = c["ssm_expand"] * c["d_model"]
    H = di // c["ssm_head_dim"]
    return di, H, c["ssm_state"], c["ssm_head_dim"], di + 2 * c["ssm_state"]


def init_params(c: dict, key) -> dict:
    """The served weights in the program's layout: bf16 matrices, f32
    vectors, per-layer leaves stacked over the layers."""
    L, D, Vp, k = c["n_layers"], c["d_model"], c["padded_vocab"], c["ssm_conv"]
    di, H, N, P, ch = _sizes(c)
    bf = jnp.bfloat16
    k_mat, k_dt, k_a = jax.random.split(key, 3)
    w = normal_params(k_mat, {
        "embed": ((Vp, D), 0.02),
        "in_proj": ((L, D, 2 * di + 2 * N + H), D ** -0.5),
        "conv_w": ((L, k, ch), k ** -0.5),
        "out_proj": ((L, di, D), di ** -0.5),
    }, bf)
    dt = jax.random.uniform(k_dt, (L, H), minval=1e-3, maxval=1e-1)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    return {
        "embed": {"tokens": w["embed"]},
        "blocks": {"sub0": {
            "ln1": ones(L, D),
            "mamba": {
                "in_proj": w["in_proj"],
                "conv_w": w["conv_w"],
                "conv_b": jnp.zeros((L, ch), bf),
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "A_log": jnp.log(jax.random.uniform(k_a, (L, H), minval=1.0, maxval=16.0)),
                "D": ones(L, H),
                "norm": ones(L, di),
                "out_proj": w["out_proj"],
            },
        }},
        "final_norm": ones(D),
    }


def _ssd(x, dt, A, Bm, Cm, Q):
    """Chunked SSD over one sequence in chunks of Q. x [S,H,P], dt [S,H],
    A [H], B and C [S,N]; S a multiple of Q. Returns y [S,H,P]."""
    S, H, P = x.shape
    nc = S // Q
    xc, dtc = x.reshape(nc, Q, H, P), dt.reshape(nc, Q, H)
    Bc, Cc = Bm.reshape(nc, Q, -1), Cm.reshape(nc, Q, -1)
    cum = jnp.cumsum(dtc * A, axis=1)  # [nc,Q,H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]  # [nc,Qi,Qj,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    hp = jax.lax.Precision.HIGHEST
    cb = jnp.einsum("cin,cjn->cij", Cc, Bc, precision=hp)
    y_in = jnp.einsum("cij,cijh,cjh,cjhp->cihp", cb, decay, dtc, xc, precision=hp)
    to_end = jnp.exp(cum[:, -1:, :] - cum)  # [nc,Q,H]
    states = jnp.einsum("cjh,cjn,cjhp->chnp", to_end * dtc, Bc, xc, precision=hp)

    def carry(h, inp):
        s, dec = inp
        return dec[:, None, None] * h + s, h  # emit the state entering the chunk

    _, h_in = jax.lax.scan(carry, jnp.zeros_like(states[0]), (states, jnp.exp(cum[:, -1, :])))
    y_off = jnp.einsum("cin,chnp,cih->cihp", Cc, h_in, jnp.exp(cum), precision=hp)
    return (y_in + y_off).reshape(S, H, P)


def _layer(c, precision, Q, h, p):
    di, H, N, P, ch = _sizes(c)
    k = c["ssm_conv"]
    S = h.shape[0]
    m = p["mamba"]
    f32 = lambda a: a.astype(jnp.float32)
    u = rms_norm(h, p["ln1"], c["norm_eps"])
    proj = mm("sd,de->se", u, m["in_proj"], precision)
    z, xbc, dt_raw = proj[:, :di], proj[:, di:di + ch], proj[:, di + ch:]
    pad = jnp.concatenate([jnp.zeros((k - 1, ch)), xbc], axis=0)
    conv = sum(pad[i:i + S] * f32(m["conv_w"][i]) for i in range(k)) + f32(m["conv_b"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(S, H, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    y = _ssd(x, dt, A, Bm, Cm, Q) + m["D"][None, :, None] * x
    y = rms_norm(y.reshape(S, di) * jax.nn.silu(z), m["norm"], c["norm_eps"])
    return rounder(precision)(h + mm("se,ed->sd", y, m["out_proj"], precision))


def logits(params: dict, tokens, c: dict, precision: str = "f32"):
    """[S] token ids -> [S, padded_vocab] float32 logits.  S is padded up to
    a multiple of the chunk here; causality leaves earlier positions
    untouched by the padding."""
    S = tokens.shape[0]
    Q = min(CHUNK, -(-S // 16) * 16)
    Sp = -(-S // Q) * Q
    tok = jnp.pad(tokens, (0, Sp - S))
    emb = params["embed"]["tokens"]
    h = rounder(precision)(emb[tok].astype(jnp.float32))
    layer = jax.checkpoint(lambda h, p: (_layer(c, precision, Q, h, p), None))
    h, _ = jax.lax.scan(layer, h, params["blocks"]["sub0"])
    h = rms_norm(h[:S], params["final_norm"], c["norm_eps"])
    return mm("sd,vd->sv", h, emb, precision)


def nll(params: dict, tokens, labels, c: dict, precision: str = "f32"):
    """Summed next-token loss of one row."""
    return nll_sum(logits(params, tokens, c, precision), labels)
