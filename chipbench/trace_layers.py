"""Run one cell traced, as ``run.py --trace 1`` does, and split its traced
window by the program's own spans, scopes and programs.

    python3 chipbench/trace_layers.py --workload <name> --seed <n> --seconds <s> [--save <out.json.gz>]

Prints what ``run.py`` prints, then one more JSON line: ``layers`` (the
split, ``chipbench.layers.reduce_layers``), ``shares`` (its per-layer
percentages) and ``clock_offset_s`` (``layers.clock_offset`` over the
spans that launch and wait for each step).  The cell's own reduction and
result line are unchanged: the profile that ``tracing.Tracer`` reads is
handed to ``chipbench.layers`` as well, before its directory is deleted.
After the run the cell's programs are compiled again for their HLO text.
``--save`` keeps the events and that text for a second look without the
chip.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402  (its import starts the set-up clock)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--save")
    ap.add_argument("--workload", required=True)
    own, rest = ap.parse_known_args(argv)
    from chipbench import harness, layers, tracing

    saved = []
    extract = tracing.extract

    def both(profile):
        saved.append(layers.raw_events(profile))
        return extract(profile)

    tracing.extract = both
    rc = run.main(["--workload", own.workload, *rest, "--trace", "1"])
    if rc or not saved:
        return rc or 1
    cell = harness.load_cell(own.workload, True)
    hlo = hlo_texts(cell)
    if own.save:
        with gzip.open(own.save, "wt") as fh:
            json.dump({"events": [list(e) for e in saved[-1]], "hlo": hlo}, fh)
    split = layers.reduce_layers(*layers.split(saved[-1]), hlo)
    kind = cell.traffic["driver"]
    sync = ("cb.step", "jit_train_step") if kind == "train" else ("cb.decode", "jit_decode_step")
    print(json.dumps({"layers": split, "shares": layers.shares(split, kind),
                      "clock_offset_s": layers.clock_offset(saved[-1], *sync)}), flush=True)
    return 0


def hlo_texts(cell) -> list[str]:
    """The compiled text of the programs a cell's window runs (the jitted
    ones; the backlog's eager row scatter has no scope), built again as its
    driver builds them.  They are compiled afresh: the persistent cache
    leaves metadata out of its key, so a program it holds may carry the op
    names of an older compile of the same code, one without the scopes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    from chipbench.harness import arch_config
    from repro.models.model import Model

    cfg = arch_config(cell.config)
    model = Model(cfg)
    mix = cell.traffic
    params = jax.eval_shape(lambda: cell.reference.init_params(cell.sizes, jax.random.PRNGKey(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if mix["driver"] == "train":
        from repro.train import optimizer as opt_mod
        from repro.train import train_step as ts

        o = cell.config["optimizer"]
        opt_cfg = opt_mod.AdamWConfig(
            lr_peak=o["lr_peak"], warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
            b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
        state = jax.eval_shape(lambda p: ts.TrainState(p, opt_mod.adamw_init(p)), params)
        step = jax.jit(ts.make_train_step(model, opt_cfg), donate_argnums=(0,))
        batch = {k: i32(mix["batch"], mix["seq"]) for k in ("tokens", "labels")}
        return [step.lower(state, batch).compile().as_text()]
    dt = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    B, L = mix["batch"], mix["max_len"]
    cache = jax.eval_shape(lambda: model.init_cache(B, L, dtype=dt))
    one = jax.eval_shape(lambda: model.init_cache(1, L, dtype=dt))
    out = [jax.jit(model.decode_step).lower(params, cache, i32(B, 1), i32(B)).compile().as_text()]
    for n in mix["prompt_len"]["buckets"]:
        out.append(jax.jit(model.prefill).lower(params, {"tokens": i32(1, n)}, one).compile().as_text())
    return out


if __name__ == "__main__":
    sys.exit(main())
