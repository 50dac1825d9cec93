"""The named scopes of the model and the train step reach the compiled
HLO's ``op_name`` metadata, through remat, the block scan and the backward
pass, so a device trace can be split by layer."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import reduced_config
from repro.models import Model
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts

SCOPES = {
    "mamba2-370m": {"mamba", "norm", "embed", "head", "optimizer"},
    "h2o-danube-3-4b": {"attn", "mlp", "norm", "embed", "head", "optimizer"},
}


def _components(hlo: str) -> set:
    """Every path component of every ``op_name``, with transforms such as
    ``transpose(jvp(head))`` unwrapped to ``head``."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        for comp in re.split(r"[/;]", name):
            m = re.fullmatch(r"(?:(?:jvp|transpose|vmap|remat)\()*(\w+)\)*", comp)
            if m:
                out.add(m.group(1))
    return out


@pytest.fixture(scope="module", params=sorted(SCOPES))
def compiled(request):
    cfg = reduced_config(request.param)
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: ts.TrainState(p, opt_mod.adamw_init(p)), params)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32) for k in ("tokens", "labels")}
    step = jax.jit(ts.make_train_step(model, opt_mod.AdamWConfig()))
    return request.param, step.lower(state, batch).compile().as_text()


def test_train_step_carries_every_scope(compiled):
    arch, hlo = compiled
    assert SCOPES[arch] <= _components(hlo)


def test_backward_ops_keep_their_scope(compiled):
    arch, hlo = compiled
    mixer = "mamba" if arch.startswith("mamba") else "attn"
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    assert any("transpose(" in p and f"/{mixer}/" in p for p in paths)
    assert any("transpose(jvp(head))" in p for p in paths)


@pytest.mark.parametrize("arch", sorted(SCOPES))
def test_serve_programs_carry_scopes(arch):
    cfg = reduced_config(arch)
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    mixer = "mamba" if arch.startswith("mamba") else "attn"
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    decode = jax.jit(model.decode_step).lower(params, cache, i32(2, 1), i32()).compile().as_text()
    one = jax.eval_shape(lambda: model.init_cache(1, 16))
    prefill = jax.jit(model.prefill).lower(params, {"tokens": i32(1, 8)}, one).compile().as_text()
    for hlo in (decode, prefill):
        assert {mixer, "norm", "embed", "head"} <= _components(hlo)


def test_moe_phases_carry_scopes_in_decode():
    """The expert layer's phases sit inside ``moe`` in the decode step of a
    hybrid with held experts and a shared expert."""
    cfg = reduced_config("granite-4.0-h-small", n_experts=8, top_k=3, experts_held=5)
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    hlo = jax.jit(model.decode_step).lower(params, cache, i32(2, 1), i32(2)).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    for phase in ("route", "dispatch", "experts", "combine", "shared"):
        assert any(f"moe/{phase}/" in p for p in paths), phase
