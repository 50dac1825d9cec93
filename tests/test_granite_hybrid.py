"""granite-4.0-h-small, cut to a CPU size (float32, two periods of 10
layers, 8 experts of which 5 held), against the plain reference in
``granite_reference.py`` on seeded random weights: the model's forward,
prefill and then decode through the hybrid SSM + KV cache, the expert
layer's held shares and its dropless dispatch, and ``ServeEngine``'s
batch isolation.

Tolerances are on the largest logit difference relative to the largest
reference logit.  Program and reference both compute in float32 but in
different orders: the chunked SSD scan against a step-by-step
recurrence, a grouped expert product against per-expert dense ones, a
softmax renormalised after top-k against a softmax over the top-k
logits.  Those differences read 3e-6 to 5e-6 over 20 layers; ``TOL`` =
1e-4 leaves them 20x of room, while the reference in bfloat16 (8 bits of
mantissa, ~4e-3 per rounding) reads ~0.1, far above it
(``test_bfloat16_reference_fails_the_tolerance``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_reference as ref
import repro.models.layers as L
import repro.models.moe as X
from repro.configs import reduced_config
from repro.models import Model
from repro.serve.engine import Request, ServeEngine

CFG = reduced_config("granite-4.0-h-small", n_experts=8, top_k=3, experts_held=5, expert_offset=2)
TOL = 1e-4
S, P = 40, 24  # tokens per row; prompt length (1.5 SSD chunks of 16)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture(scope="module")
def setup():
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, CFG.vocab_size, (2, S)), jnp.int32)
    want = jnp.stack([jax.jit(lambda t: ref.logits(params, t, CFG))(t) for t in toks])
    return model, params, toks, want


def test_config_is_the_published_period():
    assert CFG.n_layers == 20 and len(CFG.layer_kinds()) == 10
    assert CFG.layer_kinds()[5] == ("attn", "moe")
    assert all(k == ("mamba", "moe") for i, k in enumerate(CFG.layer_kinds()) if i != 5)
    assert CFG.rope_theta is None and CFG.n_held == 5 < CFG.n_experts


def test_forward_matches_reference(setup):
    model, params, toks, want = setup
    h, _ = model._embed_inputs(params, {"tokens": toks})
    h, _, _ = model._backbone(params, h, jnp.arange(S, dtype=jnp.int32))
    got = L.unembed(params["embed"], CFG, L.rms_norm(h, params["final_norm"], CFG.norm_eps))
    assert _rel(got, want) < TOL


def test_prefill_then_decode_matches_reference(setup):
    model, params, toks, want = setup
    cache = model.init_cache(2, S, dtype=jnp.float32)
    assert {"conv", "ssm"} <= set(cache["sub0"]) and {"k", "v", "pos"} <= set(cache["sub5"])
    lg, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :P]}, cache)
    assert _rel(lg[:, 0], want[:, P - 1]) < TOL
    decode = jax.jit(model.decode_step)
    for t in range(P, S):
        lg, cache = decode(params, cache, toks[:, t:t + 1], jnp.full((2,), t, jnp.int32))
        assert _rel(lg[:, 0], want[:, t]) < TOL, t


def test_bfloat16_reference_fails_the_tolerance(setup):
    _, params, toks, want = setup
    low = jax.jit(lambda t: ref.logits(params, t, CFG, jnp.bfloat16))(toks[0])
    assert _rel(low, want[0]) > TOL


def _moe_params(cfg, seed=3):
    return X.init_moe(jax.random.PRNGKey(seed), cfg)


def test_held_shares_sum_to_the_uncut_layer():
    """Four ranks of two experts each: their partial outputs, with the
    shared expert that every rank adds counted once, are the layer with
    all eight experts held."""
    full = dataclasses.replace(CFG, experts_held=None, expert_offset=0)
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, CFG.d_model))
    parts = []
    for r in range(4):
        cfg_r = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * r)
        p_r = {**p, **{k: p[k][2 * r:2 * r + 2] for k in ("w_up", "w_gate", "w_down")}}
        parts.append(X.apply_moe(p_r, cfg_r, x)[0])
    shared = L.apply_mlp(p["shared"], CFG, x)
    summed = sum(parts) - 3 * shared
    uncut = ref.moe(p, x.reshape(-1, CFG.d_model), full).reshape(x.shape)
    assert _rel(summed, uncut) < TOL
    assert _rel(X.apply_moe(p, full, x)[0], uncut) < TOL


def test_dropless_under_forced_imbalance():
    """Every token picks the same three experts, each then gets all 64
    tokens, far past a capacity dispatch's slots; nothing is dropped."""
    full = dataclasses.replace(CFG, experts_held=None, expert_offset=0)
    p = _moe_params(full)
    chosen = jnp.asarray([1, 4, 6])
    p["router"] = p["router"].at[:, chosen].add(jnp.asarray([3.0, 2.0, 1.0]))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (4, 16, CFG.d_model)) + 1.0
    _, idx, _ = X.route(p, full, x.reshape(-1, CFG.d_model))
    assert (np.sort(np.asarray(idx), axis=-1) == np.asarray(chosen)).all()
    assert x.shape[0] * x.shape[1] > X.moe_capacity(full, x.shape[0] * x.shape[1])
    for cfg in (full, CFG):  # all held; and ranks holding experts 2..6
        q = p if cfg is full else {**p, **{k: p[k][2:7] for k in ("w_up", "w_gate", "w_down")}}
        want = ref.moe(q, x.reshape(-1, CFG.d_model), cfg).reshape(x.shape)
        assert _rel(X.apply_moe(q, cfg, x)[0], want) < TOL


def test_serve_engine_batch_isolation(setup):
    _, params, _, _ = setup
    prompts = [[5, 6, 7], [9, 10, 11, 2, 5, 3, 8], [7], [1, 2, 3, 4]]
    alone = ServeEngine(CFG, params, max_len=32)  # one request per call: batch 1
    solo = {}
    for i, pr in enumerate(prompts):
        solo.update(alone.generate([Request(i, list(pr), max_new_tokens=6)]))
    batched = ServeEngine(CFG, params, max_len=32, batch_size=4).generate(
        [Request(i, list(pr), max_new_tokens=6) for i, pr in enumerate(prompts)]
    )
    assert batched == solo
