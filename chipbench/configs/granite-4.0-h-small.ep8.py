"""Plain reference of Granite 4.0-H (``granitemoehybrid``) at the sizes of
``granite-4.0-h-small.ep8.json``, and the weights the benchmark serves it
with.

Float32 throughout at HIGHEST matmul precision (``precision="fp8"``, the
control, rounds the operands and result of every product and the
residual stream).  The embedding is multiplied by
``embedding_multiplier``; each of the period's layers adds
``residual_multiplier`` times its mixer's output on RMSNorm(h), then that
multiple of its expert layer's output on RMSNorm(h).  Mixers: attention
at position ``attn_period // 2`` (grouped-query, no position embedding,
scores scaled by ``attn_scale``, causal, softmax in float32, computed in
blocks of queries), Mamba-2 elsewhere (the input projection to (z, x, B,
C, dt), a depthwise causal convolution with bias and SiLU, the SSD
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t +
D x_t by the chunked algorithm, one chunk at a time, then the gated
RMSNorm norm(y * silu(z)) and the output projection).  Expert layer: the
router's 72 logits, their top 10 and a softmax over those 10; each held
expert, a SwiGLU, applied densely to every token with its gate (zero
where the token did not pick it), one expert at a time; experts held on
other chips add nothing, as in the program; the shared SwiGLU on every
token.  The head is tied to the embedding and divided by
``logits_scaling``.  RMSNorm eps is the configuration's 1e-5 everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mm, normal_params, rms_norm, rounder

CHUNK = 256  # the reference's own SSD chunk (less for shorter sequences); any chunk gives the same y
Q_BLOCK = 512  # queries per attention block, to bound the score buffer
# The embedding's init scale.  At the program's 0.02, the tied head and the
# embedding multiplier of 12 give each token's own logit a lead of about
# 4x the spread of the others in a one-period random model, so greedy
# decoding repeats its input whatever the layers compute, and a check on
# the served tokens would read none of them.  At 1e-3 the lead is about
# one spread, short of the top of 100352 draws.
EMBED_STD = 1e-3
HI = jax.lax.Precision.HIGHEST


def _sizes(c):
    di = c["ssm_expand"] * c["d_model"]
    H = di // c["ssm_head_dim"]
    return di, H, c["ssm_state"], c["ssm_head_dim"], di + 2 * c["ssm_state"]


def _kinds(c):
    return ["attn" if i == c["attn_period"] // 2 else "mamba" for i in range(c["attn_period"])]


def init_params(c: dict, key) -> dict:
    """The served weights in the program's layout: bf16 matrices, f32
    vectors and router, per-layer leaves stacked over the scan blocks
    (one block a period)."""
    nb = c["n_layers"] // c["attn_period"]
    D, Vp, k = c["d_model"], c["padded_vocab"], c["ssm_conv"]
    di, H, N, P, ch = _sizes(c)
    Hq, G, K = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    E, Eh, F, Fs = c["n_experts"], c["experts_held"], c["d_ff"], c["shared_d_ff"]
    shapes = {"embed": ((Vp, D), EMBED_STD)}
    for i, kind in enumerate(_kinds(c)):
        s = f"sub{i}/"
        if kind == "attn":
            shapes.update({
                s + "wq": ((nb, D, Hq, K), D ** -0.5), s + "wk": ((nb, D, G, K), D ** -0.5),
                s + "wv": ((nb, D, G, K), D ** -0.5), s + "wo": ((nb, Hq, K, D), (Hq * K) ** -0.5),
            })
        else:
            shapes.update({
                s + "in_proj": ((nb, D, 2 * di + 2 * N + H), D ** -0.5),
                s + "conv_w": ((nb, k, ch), k ** -0.5),
                s + "out_proj": ((nb, di, D), di ** -0.5),
            })
        shapes.update({
            s + "w_up": ((nb, Eh, D, F), D ** -0.5), s + "w_gate": ((nb, Eh, D, F), D ** -0.5),
            s + "w_down": ((nb, Eh, F, D), F ** -0.5),
            s + "shared_up": ((nb, D, Fs), D ** -0.5), s + "shared_gate": ((nb, D, Fs), D ** -0.5),
            s + "shared_down": ((nb, Fs, D), Fs ** -0.5),
        })
    k_mat, k_router, k_dt, k_a = jax.random.split(key, 4)
    w = normal_params(k_mat, shapes, jnp.bfloat16)
    routers = normal_params(k_router, {f"sub{i}": ((nb, D, E), D ** -0.5) for i in range(c["attn_period"])},
                            jnp.float32)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    blocks = {}
    for i, kind in enumerate(_kinds(c)):
        s = f"sub{i}/"
        sub = {"ln1": ones(nb, D), "ln2": ones(nb, D), "moe": {
            "router": routers[f"sub{i}"],
            "w_up": w[s + "w_up"], "w_gate": w[s + "w_gate"], "w_down": w[s + "w_down"],
            "shared": {"w_up": w[s + "shared_up"], "w_gate": w[s + "shared_gate"],
                       "w_down": w[s + "shared_down"]},
        }}
        if kind == "attn":
            sub["attn"] = {n: w[s + n] for n in ("wq", "wk", "wv", "wo")}
        else:
            kd, ka = jax.random.split(jax.random.fold_in(k_dt, i))
            dt = jax.random.uniform(kd, (nb, H), minval=1e-3, maxval=1e-1)
            sub["mamba"] = {
                "in_proj": w[s + "in_proj"],
                "conv_w": w[s + "conv_w"],
                "conv_b": jnp.zeros((nb, ch), jnp.bfloat16),
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "A_log": jnp.log(jax.random.uniform(jax.random.fold_in(k_a, i), (nb, H), minval=1.0, maxval=16.0)),
                "D": ones(nb, H),
                "norm": ones(nb, di),
                "out_proj": w[s + "out_proj"],
            }
        blocks[f"sub{i}"] = sub
    return {"embed": {"tokens": w["embed"]}, "blocks": blocks, "final_norm": ones(D)}


def _ssd(x, dt, A, Bm, Cm, Q):
    """Chunked SSD over one sequence, one chunk of Q at a time, the state
    carried between chunks. x [S,H,P], dt [S,H], A [H], B and C [S,N]; S a
    multiple of Q. Returns y [S,H,P]."""
    S, H, P = x.shape
    nc = S // Q
    causal = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None]

    def chunk(state, inp):  # state [H,N,P]
        xc, dtc, Bc, Cc = inp
        cum = jnp.cumsum(dtc * A, axis=0)  # [Q,H]
        decay = jnp.exp(jnp.where(causal, cum[:, None, :] - cum[None, :, :], -jnp.inf))  # [Qi,Qj,H]
        cb = jnp.einsum("in,jn->ij", Cc, Bc, precision=HI)
        w = cb[:, :, None] * decay * dtc[None, :, :]  # [Qi,Qj,H]
        y = jnp.einsum("ijh,jhp->ihp", w, xc, precision=HI)
        y = y + jnp.einsum("in,hnp->ihp", Cc, state, precision=HI) * jnp.exp(cum)[:, :, None]
        to_end = jnp.exp(cum[-1] - cum) * dtc  # [Q,H]
        state = (jnp.exp(cum[-1])[:, None, None] * state
                 + jnp.einsum("jh,jn,jhp->hnp", to_end, Bc, xc, precision=HI))
        return state, y

    split = lambda a: a.reshape(nc, Q, *a.shape[1:])
    _, y = jax.lax.scan(chunk, jnp.zeros((H, Bm.shape[-1], P)), (split(x), split(dt), split(Bm), split(Cm)))
    return y.reshape(S, H, P)


def _mamba(c, precision, Q, u, m):
    di, H, N, P, ch = _sizes(c)
    k = c["ssm_conv"]
    S = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    proj = mm("sd,de->se", u, m["in_proj"], precision)
    z, xbc, dt_raw = proj[:, :di], proj[:, di:di + ch], proj[:, di + ch:]
    pad = jnp.concatenate([jnp.zeros((k - 1, ch)), xbc], axis=0)
    conv = sum(pad[i:i + S] * f32(m["conv_w"][i]) for i in range(k)) + f32(m["conv_b"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(S, H, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    y = _ssd(x, dt, -jnp.exp(m["A_log"]), Bm, Cm, Q) + m["D"][None, :, None] * x
    y = rms_norm(y.reshape(S, di) * jax.nn.silu(z), m["norm"], c["norm_eps"])
    return mm("se,ed->sd", y, m["out_proj"], precision)


def _attention(c, precision, u, a):
    """Causal GQA with no position embedding, one block of queries at a
    time."""
    S = u.shape[0]
    H, G, K = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    nq = -(-S // Q_BLOCK)
    q = mm("sd,dhk->shk", u, a["wq"], precision).reshape(S, G, H // G, K)
    q = jnp.pad(q, ((0, nq * Q_BLOCK - S), (0, 0), (0, 0), (0, 0))).reshape(nq, Q_BLOCK, G, H // G, K)
    k = mm("sd,dgk->sgk", u, a["wk"], precision)
    v = mm("sd,dgk->sgk", u, a["wv"], precision)

    def block(inp):
        qb, s0 = inp
        ok = jnp.arange(S)[None, :] <= s0 + jnp.arange(Q_BLOCK)[:, None]
        sc = mm("sgrk,tgk->grst", qb, k, precision) * c["attn_scale"]
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        return mm("grst,tgk->sgrk", pr, v, precision)

    out = jax.lax.map(block, (q, jnp.arange(nq) * Q_BLOCK)).reshape(nq * Q_BLOCK, H, K)[:S]
    return mm("shk,hkd->sd", out, a["wo"], precision)


def _swiglu(x, up, gate, down, precision):
    h = jax.nn.silu(mm("sd,df->sf", x, gate, precision)) * mm("sd,df->sf", x, up, precision)
    return mm("sf,fd->sd", h, down, precision)


def _experts(c, precision, x, p):
    """The held experts' part of the expert layer, one expert at a time,
    and the shared expert."""
    logits = jnp.einsum("sd,de->se", x, p["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, c["top_k"])
    gates = jax.nn.softmax(top, axis=-1)

    def expert(y, w):
        e, up, gate, down = w
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return y + g[:, None] * _swiglu(x, up, gate, down, precision), None

    sh = p["shared"]
    y = _swiglu(x, sh["w_up"], sh["w_gate"], sh["w_down"], precision)
    ids = c["expert_offset"] + jnp.arange(c["experts_held"])
    y, _ = jax.lax.scan(expert, y, (ids, p["w_up"], p["w_gate"], p["w_down"]))
    return y


def logits(params: dict, tokens, c: dict, precision: str = "f32"):
    """[S] token ids -> [S, padded_vocab] float32 logits.  S is padded up to
    a multiple of the chunk here; causality leaves earlier positions
    untouched by the padding."""
    S = tokens.shape[0]
    Q = min(CHUNK, -(-S // 16) * 16)
    Sp = -(-S // Q) * Q
    tok = jnp.pad(tokens, (0, Sp - S))
    q_ = rounder(precision)
    eps, r = c["norm_eps"], c["residual_multiplier"]
    emb = params["embed"]["tokens"]
    h = q_(emb[tok].astype(jnp.float32) * c["embedding_multiplier"])
    nb = c["n_layers"] // c["attn_period"]
    for b in range(nb):
        for i, kind in enumerate(_kinds(c)):
            p = jax.tree.map(lambda a: a[b], params["blocks"][f"sub{i}"])
            u = rms_norm(h, p["ln1"], eps)
            y = _attention(c, precision, u, p["attn"]) if kind == "attn" else _mamba(c, precision, Q, u, p["mamba"])
            h = q_(h + r * y)
            h = q_(h + r * _experts(c, precision, rms_norm(h, p["ln2"], eps), p["moe"]))
    h = rms_norm(h[:S], params["final_norm"], eps)
    return mm("sd,vd->sv", h, emb, precision) / c["logits_scaling"]
