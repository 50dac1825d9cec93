"""The hybrid configurations in the benchmark's CPU tests: their tiny
sizes, registered beside ``tiny.SIZES`` at collection so that the
rehearsal, the control and the compile tests size a hybrid cell as they
size the others, and ``work_hybrid`` against hand counts and against the
weights the benchmark builds."""
import json

import jax
import numpy as np
import pytest

import tiny
from chipbench import work_hybrid as wh
from chipbench.harness import HERE, load_module

# One whole period at the configuration's width; heads, experts, state
# and vocabulary shrink, 3 of 8 experts held.
tiny.SIZES.setdefault("hybrid", dict(
    n_layers=10, d_model=1024, n_heads=4, n_kv_heads=2, head_dim=128, d_ff=128, shared_d_ff=256,
    n_experts=8, experts_held=3, top_k=3, vocab_size=512, padded_vocab=512,
    ssm_state=32, ssm_head_dim=64, ssm_chunk=16,
))

TINY = dict(family="hybrid", n_layers=4, attn_period=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
            d_ff=6, shared_d_ff=10, n_experts=4, experts_held=2, top_k=2, vocab_size=30, padded_vocab=32,
            ssm_state=4, ssm_head_dim=4, ssm_expand=2, ssm_conv=3)


def test_hybrid_counts_by_hand():
    c = TINY  # 2 attention and 2 Mamba-2 layers; d_inner 16, 4 SSM heads, 24 conv channels
    assert wh.mamba_matmul_params(c) == 8 * (32 + 8 + 4) + 16 * 8 == 480
    assert wh.attn_matmul_params(c) == 2 * 8 * 2 * 4 + 2 * 8 * 1 * 4 == 192
    assert wh.expert_params(c) == 144 and wh.shared_params(c) == 240
    mats = 2 * (480 + 3 * 24) + 2 * 192 + 4 * (2 * 144 + 240) + 32 * 8
    vecs = 4 * (2 * 8 + 8 * 4) + 2 * (3 * 4 + 16) + 8
    assert wh.param_bytes(c) == mats * 2 + vecs * 4 + 2 * 24 * 2 == 8832
    assert wh.state_bytes(c) == 2 * (4 * 4 * 4 * 4 + 2 * 24 * 2) == 704
    # one expected pick of a held expert a token (top 2 of 4, 2 held)
    per_token = 2 * (2 * 480 + 2 * 192 + 4 * (8 * 4 + 240 + 144) + 32 * 8) + 2 * (288 + 144)
    assert wh.decode_token_flops(c) == per_token == 7392
    assert wh.decode_step_work(c, 3) == (3 * 7392, 8832 + 3 * 2 * 704 + 3 * 32 * 2)


@pytest.mark.parametrize("name", ["granite-4.0-h-small.ep8"])
def test_param_bytes_match_the_served_weights(name):
    cfgfile = json.loads((HERE / "configs" / f"{name}.json").read_text())
    ref = load_module(HERE / "configs" / cfgfile["reference"])
    c = cfgfile["config"]
    shapes = jax.eval_shape(lambda k: ref.init_params(c, k), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert wh.param_bytes(c) == sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert 4.8e9 < wh.param_bytes(c) < 4.9e9  # 2.41B parameters, bf16 but for vectors and router
