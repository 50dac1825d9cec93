"""Serving: prefill+decode equivalence, SWA ring buffer, ServeEngine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import reduced_config
from repro.models import Model
from repro.serve.engine import Request, ServeEngine


def _full_logits(model, params, toks):
    cfg = model.cfg
    h, _ = model._embed_inputs(params, {"tokens": toks})
    qp = jnp.arange(toks.shape[1], dtype=jnp.int32)
    h, _, _ = model._backbone(params, h, qp)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return L.unembed(params["embed"], cfg, h)


@pytest.mark.parametrize(
    "arch", ["deepseek-7b", "granite-34b", "mamba2-370m",
             "jamba-1.5-large-398b", "granite-4.0-h-small"]
)
def test_prefill_decode_equals_forward(arch):
    cfg = reduced_config(arch, capacity_factor=16.0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    B, P, D = 2, 24, 4
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P + D)), jnp.int32)
    full = _full_logits(model, params, toks)
    cache = model.init_cache(B, P + D, dtype=jnp.float32)
    lg, cache = model.prefill(params, {"tokens": toks[:, :P]}, cache)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0]), np.asarray(full[:, P - 1]), atol=2e-4, rtol=2e-4
    )
    for i in range(D):
        lg, cache = model.decode_step(
            params, cache, toks[:, P + i : P + i + 1],
            jnp.asarray(P + i, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full[:, P + i]),
            atol=2e-4, rtol=2e-4,
        )


def test_swa_ring_buffer_matches_full_when_window_covers():
    """With window >= context, SWA decode == full-attention decode."""
    cfg_swa = reduced_config("h2o-danube-3-4b", sliding_window=64)
    cfg_full = reduced_config("h2o-danube-3-4b", sliding_window=None)
    m_swa, m_full = Model(cfg_swa), Model(cfg_full)
    params = m_swa.init(jax.random.PRNGKey(0))  # same tree for both
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg_swa.vocab_size, (1, 40)), jnp.int32)
    c_swa = m_swa.init_cache(1, 64, dtype=jnp.float32)
    c_full = m_full.init_cache(1, 64, dtype=jnp.float32)
    l1, c_swa = m_swa.prefill(params, {"tokens": toks[:, :32]}, c_swa)
    l2, c_full = m_full.prefill(params, {"tokens": toks[:, :32]}, c_full)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-4, rtol=2e-4)
    for i in range(4):
        l1, c_swa = m_swa.decode_step(params, c_swa, toks[:, 32+i:33+i],
                                      jnp.asarray(32+i, jnp.int32))
        l2, c_full = m_full.decode_step(params, c_full, toks[:, 32+i:33+i],
                                        jnp.asarray(32+i, jnp.int32))
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   atol=3e-4, rtol=3e-4)


def test_swa_ring_wraps():
    """Decode past the window: ring slots are overwritten, old tokens
    leave the attention span, and logits stay finite."""
    cfg = reduced_config("h2o-danube-3-4b", sliding_window=16)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 64)), jnp.int32)
    cache = model.init_cache(1, 64, dtype=jnp.float32)  # ring length 16
    assert cache["sub0"]["k"].shape[2] == 16
    lg, cache = model.prefill(params, {"tokens": toks[:, :32]}, cache)
    for i in range(20):  # wraps the 16-slot ring
        lg, cache = model.decode_step(
            params, cache, toks[:, 32+i:33+i], jnp.asarray(32+i, jnp.int32)
        )
        assert np.isfinite(np.asarray(lg)).all()


def test_serve_engine_greedy_deterministic():
    cfg = reduced_config("deepseek-7b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng1 = ServeEngine(cfg, params, max_len=64)
    eng2 = ServeEngine(cfg, params, max_len=64)
    reqs1 = [Request(0, [5, 6, 7], max_new_tokens=8),
             Request(1, [9, 10], max_new_tokens=8)]
    reqs2 = [Request(0, [5, 6, 7], max_new_tokens=8),
             Request(1, [9, 10], max_new_tokens=8)]
    out1 = eng1.generate(reqs1)
    out2 = eng2.generate(reqs2)
    assert out1 == out2
    assert all(len(v) == 8 for v in out1.values())
    assert all(0 <= t < cfg.vocab_size for v in out1.values() for t in v)
