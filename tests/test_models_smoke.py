"""Per-architecture smoke tests: REDUCED same-family configs, one forward
and one optimizer step on CPU, asserting shapes and finiteness.
(Full configs are exercised only via the dry-run — no allocation.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.models import Model
from repro.train.data import make_batch
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def _batch(cfg, B=2, S=64):
    return {
        k: jnp.asarray(v)
        for k, v in make_batch(cfg, B, S, step=0, seed=0).items()
    }


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_train_step(arch):
    cfg = reduced_config(arch)
    model = Model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, AdamWConfig(total_steps=10)))
    batch = _batch(cfg)
    new_state, metrics = step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_state.opt.step) == 1
    # params actually changed
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                           b.astype(jnp.float32)))),
        state.params, new_state.params,
    )
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_forward_shapes(arch):
    cfg = reduced_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch(cfg, B=2, S=64)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    assert float(metrics["n_tokens"]) > 0


def test_loss_decreases_on_repeated_batch():
    """Overfit one batch for a few steps: loss must drop (end-to-end sanity
    of grads + optimizer across the whole stack)."""
    cfg = reduced_config("deepseek-7b")
    model = Model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(
        make_train_step(model, AdamWConfig(lr_peak=3e-3, warmup_steps=1,
                                           total_steps=1000))
    )
    batch = _batch(cfg, B=4, S=32)
    losses = []
    for _ in range(12):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3


def test_full_param_counts_match_spec():
    """Full configs (abstract shapes only) land near their nameplate sizes."""
    expected = {
        "qwen3-32b": 33e9,
        "deepseek-7b": 7e9,
        "granite-34b": 34e9,
        "h2o-danube-3-4b": 4e9,
        "llava-next-mistral-7b": 7.3e9,
        "mamba2-370m": 0.37e9,
        "jamba-1.5-large-398b": 398e9,
        "qwen3-moe-30b-a3b": 30.5e9,
        "hubert-xlarge": 0.96e9,
        "moonshot-v1-16b-a3b": 28e9,  # 48L as assigned (HF model uses 27L)
        "granite-4.0-h-small": 32.2e9,
    }
    for arch, want in expected.items():
        cfg = get_config(arch)
        specs = Model(cfg).param_specs()
        total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(specs))
        assert abs(total - want) / want < 0.12, (arch, total, want)


def test_microbatch_accumulation_matches_single():
    cfg = reduced_config("deepseek-7b")
    model = Model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = _batch(cfg, B=4, S=32)
    s1, m1 = jax.jit(make_train_step(model, AdamWConfig()))(state, batch)
    state2 = init_train_state(model, jax.random.PRNGKey(0))
    s2, m2 = jax.jit(
        make_train_step(model, AdamWConfig(), num_microbatches=2)
    )(state2, batch)
    # same data -> nearly identical update (fp accumulation order differs)
    d = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                           b.astype(jnp.float32)))),
        s1.params, s2.params,
    )
    assert max(jax.tree.leaves(d)) < 5e-3
