"""Plain reference of Granite 4.0-H (``granitemoehybrid``) for the tests.

One sequence at a time, straightforward ``jax.numpy`` at HIGHEST matmul
precision, no kernels, no cache, no batching, written from the published
config's equations and importing nothing of the model code.  It reads the
weights in the program's layout (``Model.init``), and ``cfg`` only for
sizes and constants.

    h = E[tok] * embedding_multiplier
    per layer:  h += r * mixer(RMSNorm(h)),  h += r * (moe(x) + shared(x)),
                x = RMSNorm(h), r = residual_multiplier
    logits = RMSNorm(h) @ E^T / logits_scaling

Mixer: attention at position ``attn_period // 2`` of each period, Mamba-2
elsewhere.  Attention: GQA with no position embedding, scores scaled by
``attn_scale``.  Mamba-2: the recurrence h_t = exp(dt_t A) h_{t-1} +
dt_t x_t B_t^T, y_t = h_t C_t + D x_t, one step at a time, then the gated
RMSNorm norm(y * silu(z)).  Router: the top-k of the logits over all
experts, a softmax over those k; held experts ``[expert_offset,
expert_offset + experts_held)`` are each applied densely to every token
with its gate (zero where unrouted), absent experts add nothing.

``dtype=jnp.bfloat16`` rounds every product's operands and result, and
the residual stream, to bfloat16: the precision below the tests' float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _rounder(dtype):
    if dtype == jnp.float32:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


def _mm(spec, a, b, rd):
    return rd(jnp.einsum(spec, rd(a.astype(jnp.float32)), rd(b.astype(jnp.float32)), precision=HI))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, up, gate, down, rd):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", x, gate, rd)) * _mm("td,df->tf", x, up, rd), down, rd)


def moe(p, x, cfg, rd=_rounder(jnp.float32)):
    """One layer's experts and shared expert for tokens ``x`` [T,D]."""
    logits = jnp.einsum("td,de->te", x, p["router"].astype(jnp.float32), precision=HI)
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top, axis=-1)  # [T,K]
    held = cfg.n_experts if cfg.experts_held is None else cfg.experts_held
    y = jnp.zeros_like(x)
    for e in range(held):
        g = jnp.sum(jnp.where(idx == cfg.expert_offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * _swiglu(x, p["w_up"][e], p["w_gate"][e], p["w_down"][e], rd)
    if "shared" in p:
        s = p["shared"]
        y = y + _swiglu(x, s["w_up"], s["w_gate"], s["w_down"], rd)
    return y


def _attention(p, u, cfg, rd):
    S = u.shape[0]
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm("sd,dhk->shk", u, p["wq"], rd).reshape(S, G, H // G, K)
    k = _mm("sd,dgk->sgk", u, p["wk"], rd)
    v = _mm("sd,dgk->sgk", u, p["wv"], rd)
    sc = jnp.einsum("sgrk,tgk->grst", q, k, precision=HI) * cfg.attn_scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    out = jnp.einsum("grst,tgk->sgrk", pr, v, precision=HI).reshape(S, H, K)
    return _mm("shk,hkd->sd", out, p["wo"], rd)


def _mamba(p, u, cfg, rd):
    S = u.shape[0]
    di, N, P, k = cfg.ssm_expand * cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_conv
    H = di // P
    proj = _mm("sd,de->se", u, p["in_proj"], rd)
    z, xbc, dt_raw = proj[:, :di], proj[:, di:2 * di + 2 * N], proj[:, 2 * di + 2 * N:]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    w = p["conv_w"].astype(jnp.float32)
    conv = jax.nn.silu(sum(pad[i:i + S] * w[i] for i in range(k)) + p["conv_b"].astype(jnp.float32))
    x, Bm, Cm = conv[:, :di].reshape(S, H, P), conv[:, di:di + N], conv[:, di + N:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(state, inp):  # state [H,P,N]
        x_t, dt_t, B_t, C_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * B_t
        return state, jnp.einsum("hpn,n->hp", state, C_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, dt, Bm, Cm))
    y = y + p["D"][None, :, None] * x
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return _mm("se,ed->sd", y, p["out_proj"], rd)


def logits(params, tokens, cfg, dtype=jnp.float32):
    """[S] token ids -> [S, padded_vocab] logits."""
    rd = _rounder(dtype)
    emb = params["embed"]["tokens"].astype(jnp.float32)
    h = rd(emb[tokens] * cfg.embedding_multiplier)
    r, eps = cfg.residual_multiplier, cfg.norm_eps
    blocks = params["blocks"]
    n_blocks = jax.tree.leaves(blocks)[0].shape[0]
    for b in range(n_blocks):
        for i in range(cfg.attn_period):
            p = jax.tree.map(lambda a: a[b], blocks[f"sub{i}"])
            u = _rms(h, p["ln1"], eps)
            if i == cfg.attn_period // 2:
                h = rd(h + r * _attention(p["attn"], u, cfg, rd))
            else:
                h = rd(h + r * _mamba(p["mamba"], u, cfg, rd))
            h = rd(h + r * moe(p["moe"], _rms(h, p["ln2"], eps), cfg, rd))
    h = _rms(h, params["final_norm"], eps)
    return _mm("sd,vd->sv", h, emb, rd) / cfg.logits_scaling
