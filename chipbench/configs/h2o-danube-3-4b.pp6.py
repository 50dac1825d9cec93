"""Plain reference of an H2O-Danube3 decoder stack (Llama/Mistral style,
arXiv:2401.16818) at the sizes of ``h2o-danube-3-4b.pp6.json``, and the
weights the benchmark serves it with.

Float32 throughout at HIGHEST matmul precision (``precision="fp8"``, the
control, rounds the operands and result of every product and the
residual stream).  Each layer: RMSNorm,
grouped-query attention (32 query heads sharing 8 key/value heads of width
120) with rotary embeddings on the first 120 channels split in halves,
causal with a sliding window, softmax in float32; the output projection;
RMSNorm and a SiLU-gated MLP; both added to the residual.  A final RMSNorm
and an untied head.  Departures, as the configuration states: RMSNorm eps
1e-6; rope theta 1e4.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mm, nll_sum, normal_params, rms_norm, rounder

Q_BLOCK = 1024  # queries per attention block, to bound the score buffer


def init_params(c: dict, key) -> dict:
    """The served weights in the program's layout: bf16 matrices, f32
    vectors, per-layer leaves stacked over the layers."""
    L, D, Vp = c["n_layers"], c["d_model"], c["padded_vocab"]
    H, G, K, F = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    w = normal_params(key, {
        "tokens": ((Vp, D), 0.02),
        "unembed": ((D, Vp), D ** -0.5),
        "wq": ((L, D, H, K), D ** -0.5),
        "wk": ((L, D, G, K), D ** -0.5),
        "wv": ((L, D, G, K), D ** -0.5),
        "wo": ((L, H, K, D), (H * K) ** -0.5),
        "w_up": ((L, D, F), D ** -0.5),
        "w_gate": ((L, D, F), D ** -0.5),
        "w_down": ((L, F, D), F ** -0.5),
    }, jnp.bfloat16)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    return {
        "embed": {"tokens": w["tokens"], "unembed": w["unembed"]},
        "blocks": {"sub0": {
            "ln1": ones(L, D),
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": ones(L, D),
            "mlp": {k: w[k] for k in ("w_up", "w_gate", "w_down")},
        }},
        "final_norm": ones(D),
    }


def _rope(x, pos, theta):
    """x [S,heads,K]: rotate channel pairs (i, i + K/2) by pos * theta^(-i/(K/2))."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(c, precision, q, k, v):
    """q [S,H,K], k and v [S,G,K] -> [S,H,K]; causal, window W."""
    S, H, K = q.shape
    G = k.shape[1]
    W = c["sliding_window"] or S
    qg = q.reshape(S, G, H // G, K)
    kv_pos = jnp.arange(S)
    out = []
    for s0 in range(0, S, Q_BLOCK):
        qb = qg[s0:s0 + Q_BLOCK]
        qp = jnp.arange(s0, s0 + qb.shape[0])[:, None]
        ok = (kv_pos[None, :] <= qp) & (kv_pos[None, :] > qp - W)
        sc = mm("sgrk,tgk->grst", qb, k, precision) * K ** -0.5
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        out.append(mm("grst,tgk->sgrk", pr, v, precision))
    return jnp.concatenate(out, axis=0).reshape(S, H, K)


def _layer(c, precision, h, p):
    eps = c["norm_eps"]
    q_ = rounder(precision)
    pos = jnp.arange(h.shape[0])
    a = p["attn"]
    u = rms_norm(h, p["ln1"], eps)
    q = _rope(mm("sd,dhk->shk", u, a["wq"], precision), pos, c["rope_theta"])
    k = _rope(mm("sd,dgk->sgk", u, a["wk"], precision), pos, c["rope_theta"])
    v = mm("sd,dgk->sgk", u, a["wv"], precision)
    h = q_(h + mm("shk,hkd->sd", _attention(c, precision, q, k, v), a["wo"], precision))
    u = rms_norm(h, p["ln2"], eps)
    f = p["mlp"]
    g = jax.nn.silu(mm("sd,df->sf", u, f["w_gate"], precision))
    return q_(h + mm("sf,fd->sd", g * mm("sd,df->sf", u, f["w_up"], precision), f["w_down"], precision))


def logits(params: dict, tokens, c: dict, precision: str = "f32"):
    """[S] token ids -> [S, padded_vocab] float32 logits."""
    h = rounder(precision)(params["embed"]["tokens"][tokens].astype(jnp.float32))
    layer = jax.checkpoint(lambda h, p: (_layer(c, precision, h, p), None))
    h, _ = jax.lax.scan(layer, h, params["blocks"]["sub0"])
    h = rms_norm(h, params["final_norm"], c["norm_eps"])
    return mm("sd,dv->sv", h, params["embed"]["unembed"], precision)


def nll(params: dict, tokens, labels, c: dict, precision: str = "f32"):
    """Summed next-token loss of one row."""
    return nll_sum(logits(params, tokens, c, precision), labels)
