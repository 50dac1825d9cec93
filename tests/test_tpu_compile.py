"""Compile the Pallas kernels for a described (not attached) TPU v5e.

Nothing runs: the TPU compiler lowers each kernel at real widths and
raises what the chip's compiler would raise (unsupported primitives,
scoped-VMEM overflow).  The topology is described inside a fixture, so a
process that cannot describe it skips these tests and every pytest worker
collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


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


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("T", [4096, 32768])
def test_flash_attention_compiles(one_chip, T):
    # h2o-danube-3-4b-like heads (32 query, 8 kv) at head_dim 128, bf16
    B, H, G, K = 1, 32, 8, 128
    bf16, i32 = jnp.bfloat16, jnp.int32
    _compile(
        lambda q, k, v, qp, kp: flash_attention_pallas(
            q, k, v, qp, kp, causal=True, interpret=False
        ),
        one_chip,
        ((B, T, H, K), bf16), ((B, T, G, K), bf16), ((B, T, G, K), bf16),
        ((T,), i32), ((T,), i32),
    )


def test_ssd_scan_compiles(one_chip):
    # mamba2-370m: d_inner 2048 / head_dim 64 = 32 heads, state 128
    B, S, H, P, N = 1, 4096, 32, 64, 128
    f32 = jnp.float32
    _compile(
        lambda x, dt, A, Bm, Cm: ssd_scan_pallas(
            x, dt, A, Bm, Cm, chunk=128, interpret=False
        ),
        one_chip,
        ((B, S, H, P), f32), ((B, S, H), f32), ((H,), f32),
        ((B, S, N), f32), ((B, S, N), f32),
    )


@pytest.mark.parametrize("D", [1024, 6144])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_compiles(one_chip, D, dtype):
    _compile(
        lambda x, s: rmsnorm_pallas(x, s, interpret=False),
        one_chip,
        ((8, 2048, D), dtype), ((D,), jnp.float32),
    )
