"""The SSM decode state's stored order, [B, H, P, N] with N minor.

On the CPU: decode steps from a prefilled cache follow ``ssd_chunked``
over the whole sequence, and the cache's ``ssm`` leaf has that order.
For a described v5e: the decode step at mamba2-370m serving sizes
updates the state without relayouting it, and needs no temporary buffer
of a layer's size.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.models.mamba as M
from repro.configs import get_config, reduced_config
from repro.models import Model


def _ssd_inputs(p, cfg, x):
    """The chunked scan's inputs for ``x`` [B,S,D], as ``apply_mamba``
    builds them."""
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    _, xbc, dt_raw = M._split_proj(cfg, proj)
    xbc = M._causal_conv(xbc, p["conv_w"], p["conv_b"]).astype(x.dtype)
    xh = xbc[..., :di].reshape(*x.shape[:2], H, P)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return xh, dt, -jnp.exp(p["A_log"]), xbc[..., di:di + N], xbc[..., di + N:]


@pytest.mark.parametrize("k", [1, 5])
def test_decode_steps_follow_chunked_scan(k):
    cfg = reduced_config("mamba2-370m", dtype="float32")
    p = M.init_mamba(jax.random.PRNGKey(0), cfg)
    B, S = 2, 19
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S + k, cfg.d_model))

    _, cache = M.apply_mamba(p, cfg, x[:, :S], return_cache=True)
    ys = []
    for t in range(S, S + k):
        y, cache = M.apply_mamba_decode(p, cfg, x[:, t:t + 1], cache)
        ys.append(y)

    full, _ = M.apply_mamba(p, cfg, x)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, axis=1)), np.asarray(full[:, S:]),
        atol=2e-4, rtol=2e-4,
    )
    _, state = M.ssd_chunked(*_ssd_inputs(p, cfg, x), cfg.ssm_chunk)  # [B,H,N,P]
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(cache["ssm"], -1, -2)), np.asarray(state),
        atol=2e-4, rtol=2e-4,
    )


@pytest.mark.parametrize("arch", ["mamba2-370m", "jamba-1.5-large-398b"])
def test_cache_ssm_leaf_is_n_minor(arch):
    cfg = reduced_config(arch, capacity_factor=16.0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    want = (model.n_blocks, B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    toks = jnp.zeros((B, S), jnp.int32)
    cache = model.init_cache(B, S + 1, dtype=jnp.float32)
    _, filled = model.prefill(params, {"tokens": toks}, cache)
    _, stepped = model.decode_step(
        params, filled, toks[:, :1], jnp.asarray(S, jnp.int32)
    )
    for tree in (cache, filled, stepped):
        ssm = [v["ssm"] for v in tree.values() if "ssm" in v]
        assert ssm and all(a.shape == want and a.dtype == jnp.float32 for a in ssm)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_decode_step_keeps_state_layout_on_v5e(one_chip):
    # mamba2-370m served at batch 128, max_len 5120, the conv cache in bf16
    cfg = get_config("mamba2-370m")
    model = Model(cfg)
    B = 128

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = spec(jax.eval_shape(lambda: model.init_cache(B, 5120, jnp.bfloat16)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(params, cache, tok, pos).compile()

    layer = B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    state_copies = [
        m.group(0)
        for m in re.finditer(r"= f32\[([\d,]*)\]\S* copy[\w-]*\(", compiled.as_text())
        if int(np.prod([int(d) for d in m.group(1).split(",") if d])) == layer
    ]
    assert state_copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer * 4  # f32 bytes
