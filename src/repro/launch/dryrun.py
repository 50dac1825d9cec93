import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, and fits — without real hardware.

The two lines above MUST run before any jax import (jax locks the device
count at first init).  Placeholder host devices stand in for the production
mesh: 16x16 = 256 chips single-pod, 2x16x16 = 512 chips across two pods.

Per cell this script:
  1. builds abstract (ShapeDtypeStruct) params/opt-state/inputs — nothing
     is allocated;
  2. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``;
  3. records ``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs /
     bytes) and the per-device collective bytes parsed from the HLO —
     the inputs to EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k \
      [--multi-pod] [--out results/dryrun/cell.json]
  python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from ..configs import SHAPES, applicable_shapes, get_config, list_archs
from ..configs.base import ArchConfig, ShapeCell
from ..models.model import Model, active_params
from ..parallel import sharding as sh
from ..train.optimizer import AdamWConfig
from ..train.train_step import init_train_state, make_serve_steps, make_train_step
from . import analysis
from .mesh import make_production_mesh


# --------------------------------------------------------------------------
# input specs (abstract stand-ins for every model input)
# --------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for the batch of one step."""
    B, S = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    if cfg.family == "audio":
        return {
            "frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
    if cfg.family == "vlm":
        Ti = cfg.vlm_img_tokens
        return {
            "tokens": jax.ShapeDtypeStruct((B, S - Ti), i32),
            "labels": jax.ShapeDtypeStruct((B, S - Ti), i32),
            "patch_embeds": jax.ShapeDtypeStruct(
                (B, Ti, cfg.frontend_dim), jnp.bfloat16
            ),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
        "labels": jax.ShapeDtypeStruct((B, S), i32),
    }


def _prefill_specs(cfg: ArchConfig, cell: ShapeCell) -> dict:
    specs = input_specs(cfg, cell)
    specs.pop("labels", None)
    return specs


def _token_specs(cell: ShapeCell) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32)


# --------------------------------------------------------------------------
# per-cell dry run
# --------------------------------------------------------------------------


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool,
    verbose: bool = True,
    mesh=None,
    cfg: ArchConfig | None = None,
    opts: tuple = (),
) -> dict:
    cfg = cfg or get_config(arch)
    cell = SHAPES[shape]
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    from ..parallel import opt_flags

    opt_flags.reset()
    b = sh.batch_axes(mesh, cell.global_batch)
    opt_flags.set_flags(batch_axes=b)
    if "sp" in opts and cell.seq_len % max(mesh.shape.get("model", 1), 1) == 0:
        # §Perf: sequence-parallel residual stream (shard S over `model`)
        model.act_spec = sh.P(b, "model", None)
        opt_flags.set_flags(sp=True)
    if "mamba_heads" in opts:
        opt_flags.set_flags(mamba_heads=True)
    if "moe_a2a" in opts:
        opt_flags.set_flags(moe_a2a=True, mesh=mesh)
    if "sp_sub" in opts:
        opt_flags.set_flags(sp_sub=True)

    t0 = time.time()
    with mesh:
        if cell.kind == "train":
            state_specs = jax.eval_shape(
                lambda k: init_train_state(model, k), key
            )
            p_sh = sh.param_shardings(cfg, state_specs.params, mesh)
            state_sh = type(state_specs)(
                params=p_sh,
                opt=type(state_specs.opt)(
                    step=sh.replicated(mesh),
                    m=sh.param_shardings(cfg, state_specs.opt.m, mesh),
                    v=sh.param_shardings(cfg, state_specs.opt.v, mesh),
                ),
                error_feedback=None,
            )
            batch_specs = input_specs(cfg, cell)
            b_sh = sh.batch_shardings(cfg, batch_specs, mesh)
            step_fn = make_train_step(model, AdamWConfig())
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, b_sh),
                out_shardings=(state_sh, sh.replicated(mesh)),
                donate_argnums=(0,),
            ).lower(state_specs, batch_specs)
        else:
            params_specs = model.param_specs(key)
            p_sh = sh.param_shardings(cfg, params_specs, mesh)
            cache_specs = jax.eval_shape(
                lambda: model.init_cache(
                    cell.global_batch, cell.seq_len, dtype=jnp.bfloat16
                )
            )
            c_sh = sh.cache_shardings(cfg, cache_specs, mesh)
            prefill_step, decode_step = make_serve_steps(model)
            b = sh.batch_axes(mesh, cell.global_batch)
            logits_sh = sh.NamedSharding(
                mesh,
                sh.P(b, None, sh.maybe(mesh, cfg.padded_vocab, "model")),
            )
            if cell.kind == "prefill":
                batch_specs = _prefill_specs(cfg, cell)
                b_sh = sh.batch_shardings(cfg, batch_specs, mesh)
                lowered = jax.jit(
                    prefill_step,
                    in_shardings=(p_sh, b_sh, c_sh),
                    out_shardings=(logits_sh, c_sh),
                    donate_argnums=(2,),
                ).lower(params_specs, batch_specs, cache_specs)
            else:  # decode
                tok = _token_specs(cell)
                tok_sh = sh.NamedSharding(mesh, sh.P(b, None))
                pos = jax.ShapeDtypeStruct((), jnp.int32)
                lowered = jax.jit(
                    decode_step,
                    in_shardings=(p_sh, c_sh, tok_sh, sh.replicated(mesh)),
                    out_shardings=(logits_sh, c_sh),
                    donate_argnums=(1,),
                ).lower(params_specs, cache_specs, tok, pos)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = analysis.extract_cost(compiled)  # loop-UNAWARE, kept for ref
    from . import hlo_cost

    mc = hlo_cost.analyze(compiled.as_text())  # loop-aware per-device cost
    n_active = active_params(cfg, model.param_specs(key))
    terms = analysis.RooflineTerms(
        arch=arch,
        shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        n_devices=n_dev,
        hlo_flops=mc.flops,
        hlo_bytes=mc.bytes,
        coll_bytes=mc.coll_bytes,
        coll_breakdown={k: int(v) for k, v in mc.coll.items()},
        model_flops=analysis.model_flops_for(cfg, cell, n_active),
        peak_memory_bytes=float(
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes
        ),
    )
    result = {
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "xla_cost_loop_unaware": xla_cost,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        **terms.to_dict(),
    }
    if verbose:
        print(json.dumps(result, indent=2, default=str))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument(
        "--opt", default="",
        help="comma-separated §Perf optimizations (e.g. sp)",
    )
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    if args.all:
        outdir = Path(args.out or "results/dryrun")
        outdir.mkdir(parents=True, exist_ok=True)
        mesh_tag = "multi" if args.multi_pod else "single"
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                path = outdir / f"{arch}__{shape}__{mesh_tag}.json"
                if args.skip_existing and path.exists():
                    print(f"skip {path}")
                    continue
                print(f"=== {arch} x {shape} x {mesh_tag} ===", flush=True)
                try:
                    res = run_cell(arch, shape, args.multi_pod, verbose=False,
                                   opts=opts)
                except Exception as e:  # record failures for triage
                    res = {
                        "ok": False,
                        "error": repr(e),
                        "traceback": traceback.format_exc(),
                    }
                    print(f"FAILED: {e!r}", flush=True)
                path.write_text(json.dumps(res, indent=2, default=str))
                print(
                    f"-> {path} ok={res.get('ok')} "
                    f"compile={res.get('compile_s')}s",
                    flush=True,
                )
        return

    res = run_cell(args.arch, args.shape, args.multi_pod, opts=opts)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(res, indent=2, default=str))


if __name__ == "__main__":
    main()
