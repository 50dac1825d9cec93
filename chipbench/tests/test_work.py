"""The work functions against hand counts at small sizes, and against the
weights the benchmark actually builds at the cells' sizes."""
import json

import jax
import numpy as np
import pytest

from chipbench import work
from chipbench.harness import HERE, load_module
from chipbench.peaks import peaks_for

TINY_SSM = dict(family="ssm", n_layers=2, d_model=64, vocab_size=250, padded_vocab=256,
                ssm_state=16, ssm_head_dim=16, ssm_expand=2, ssm_conv=4, tie_embeddings=True)


def test_ssm_counts_by_hand():
    c = TINY_SSM
    # in_proj 64 x (2*128 + 2*16 + 8) and out_proj 128 x 64
    assert work.layer_matmul_params(c) == 64 * 296 + 128 * 64 == 27136
    assert work.matmul_params(c) == 2 * 27136 + 256 * 64
    assert work.ssd_step_flops(c) == 4 * 8 * 16 * 16 + 2 * 8 * 16
    assert work.conv_flops(c) == 2 * 4 * 160
    assert work.train_flops_per_token(c, 64) == 3 * (2 * 70656 + 2 * (8448 + 1280))
    flops, nbytes = work.decode_step_work(c, 3)
    assert flops == 3 * (2 * 70656 + 2 * (8448 + 1280))
    weights = (2 * 27136 + 256 * 64 + 2 * 4 * 160) * 2 + (64 + 2 * (64 + 24 + 128)) * 4 + 2 * 160 * 2
    state = 2 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    assert nbytes == weights + 3 * 2 * state + 3 * 256 * 2
    flops, nbytes = work.prefill_work(c, 10)
    assert flops == 10 * (2 * 2 * 27136 + 2 * (8448 + 1280)) + 2 * 256 * 64
    assert nbytes == weights + state + 256 * 2


def test_kernel_counts_by_hand():
    assert work.attention_ctx_sum(4, None) == 10
    assert work.attention_ctx_sum(4, 2) == 7
    assert work.attention_work(1, 4, 2, 1, 8) == (4 * 2 * 8 * 10, 4 * 6 * 8 * 2)
    assert work.ssd_scan_work(1, 4, 1, 2, 3, 2) == (80 + 48 + 48, 50 * 4)
    assert work.rmsnorm_work(3, 4) == (48, 64)
    assert work.least_time(10.0, 40.0, 10.0, 10.0) == 4.0


@pytest.mark.parametrize("name", ["mamba2-370m", "h2o-danube-3-4b.pp6"])
def test_param_bytes_match_the_served_weights(name):
    cfgfile = json.loads((HERE / "configs" / f"{name}.json").read_text())
    ref = load_module(HERE / "configs" / cfgfile["reference"])
    c = cfgfile["config"]
    shapes = jax.eval_shape(lambda k: ref.init_params(c, k), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert work.param_bytes(c) == sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)


def test_cells_model_flops():
    m = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())["config"]
    assert work.matmul_params(m) == 48 * (1024 * 4384 + 2048 * 1024) + 50432 * 1024
    d = json.loads((HERE / "configs" / "h2o-danube-3-4b.pp6.json").read_text())["config"]
    assert work.matmul_params(d) == 4 * 154_828_800 + 122_880_000
    # causal attention at 4096 adds about 0.38 GFLOP per trained token
    att = work.train_flops_per_token(d, 4096) - 6 * work.matmul_params(d)
    assert 0.37e9 < att < 0.39e9


def test_unknown_device_is_an_error():
    assert peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
