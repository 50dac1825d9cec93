"""Every cell's step programs compile at the cell's real sizes for a
described (not attached) v5e, and fit its memory: the train steps with
their donated state, and the serve prefill at the longest prompt bucket
and decode at the cell's batch.  A compile is not a chip run; it finds
what the chip's compiler would refuse before chip time is spent."""
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import arch_config, benchmark, load_cell
from chipbench.peaks import peaks_for

CELLS = [w["name"] for w in benchmark()["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe the chip skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert used < peaks_for("TPU v5 lite").hbm_bytes, used


@pytest.mark.parametrize("name", CELLS)
def test_cell_compiles_for_v5e(one_chip, name):
    from repro.models.model import Model
    from repro.train import optimizer as om
    from repro.train import train_step as ts

    cell = load_cell(name, False)
    cfg = arch_config(cell.config)
    model = Model(cfg)
    mix = cell.traffic
    params = jax.eval_shape(lambda k: cell.reference.init_params(cell.sizes, k), jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if mix["driver"] == "train":
        state = jax.eval_shape(lambda p: ts.TrainState(p, om.adamw_init(p)), params)
        batch = {"tokens": i32(mix["batch"], mix["seq"]), "labels": i32(mix["batch"], mix["seq"])}
        step = jax.jit(ts.make_train_step(model, om.AdamWConfig()), donate_argnums=(0,))
        _fits(step.lower(_shapes(state, one_chip), batch).compile())
        return
    p = _shapes(params, one_chip)
    one = _shapes(jax.eval_shape(lambda: model.init_cache(1, mix["max_len"], jnp.bfloat16)), one_chip)
    longest = max(mix["prompt_len"]["buckets"])
    _fits(jax.jit(model.prefill).lower(p, {"tokens": i32(1, longest)}, one).compile())
    B = mix["batch"]
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(B, mix["max_len"], jnp.bfloat16)), one_chip)
    _fits(jax.jit(model.decode_step).lower(p, cache, i32(B, 1), i32(B)).compile())
