"""Training cells: the program's jitted ``make_train_step`` with its state
donated, driven over a time window.

Set-up builds one state from the benchmark's weights, drives it through
the first three steps with the window's own call and feed (the first one
compiles), reads what the comparison needs, and hands the same state to
the window.  After the window the state is freed and the plain reference
follows the same three steps in float32.
"""
from __future__ import annotations

import gc
import json
import statistics
import time

import numpy as np

from chipbench import gen
from chipbench.harness import arch_config, mark, memory_peak_bytes, program_bytes, say, shapes_of
from chipbench.reference import AdamW, flatten, seed_key, unflatten
from chipbench.tracing import Tracer

CHECKED_STEPS = 3
TRACE_S = 6.0  # the traced sub-window: the last seconds of the window


def _leaf_norms(tree) -> dict:
    import jax.numpy as jnp

    return {k: jnp.linalg.norm(v.astype(jnp.float32).ravel()) for k, v in flatten(tree).items()}


def check_layout(model, params) -> None:
    """The benchmark's weights must match the program's own layout."""
    import jax

    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    fw, fg = flatten(want), flatten(got)
    bad = [k for k in sorted(set(fw) | set(fg))
           if k not in fw or k not in fg or (fw[k].shape, fw[k].dtype) != (fg[k].shape, fg[k].dtype)]
    if bad:
        raise ValueError(f"benchmark weights differ from the program's layout at {bad[:5]}")


def setup(cell, seed: int):
    """Build the one state and compiled step, and drive them through the
    first ``CHECKED_STEPS`` steps with the window's own call and feed.
    Returns (state, one_step, readings): ``one_step(state, i)`` runs step
    ``i`` (its jitted step is ``one_step.step``); readings hold the losses,
    the clipped first gradient's norm per leaf (from AdamW's first moment
    after one step) and each leaf's change after the checked steps."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import Model
    from repro.train import optimizer as opt_mod
    from repro.train import train_step as ts

    sizes, mix, o = cell.sizes, cell.traffic, cell.config["optimizer"]
    cfg = arch_config(cell.config)
    model = Model(cfg)
    opt_cfg = opt_mod.AdamWConfig(
        lr_peak=o["lr_peak"], warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
        clip_norm=o["clip_norm"],
    )
    key = seed_key(seed)
    init = jax.jit(lambda k: cell.reference.init_params(sizes, k))
    params = init(key)
    check_layout(model, params)
    state = jax.block_until_ready(jax.jit(lambda p: ts.TrainState(p, opt_mod.adamw_init(p)))(params))
    del params
    mark("weights and optimizer state")
    step = jax.jit(ts.make_train_step(model, opt_cfg), donate_argnums=(0,))

    def one(state, i):
        with jax.profiler.TraceAnnotation("cb.data"):
            b = gen.train_batch(mix, cfg.vocab_size, seed, i)
        with jax.profiler.TraceAnnotation("cb.step"):
            state, metrics = step(state, {k: jnp.asarray(v) for k, v in b.items()})
            loss = float(metrics["loss"])  # the step has ended on the device
        return state, loss

    one.step = step
    losses = []
    state, loss = one(state, 0)
    losses.append(loss)
    mark("first step")
    grad1 = {k: float(v) / (1.0 - o["b1"]) for k, v in jax.jit(_leaf_norms)(state.opt.m).items()}
    for i in range(1, CHECKED_STEPS):
        state, loss = one(state, i)
        losses.append(loss)
    change = jax.jit(lambda p, k: _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, init(k))))(state.params, key)
    readings = {"losses": losses, "grad1": grad1, "change": {k: float(v) for k, v in change.items()}}
    mark("checked steps")
    return state, one, readings


def run(cell, seed: int, seconds: float, trace: bool, clock0: float, counter=None) -> tuple[dict, dict]:
    import jax

    mix = cell.traffic
    tokens_per_step = mix["batch"] * mix["seq"]
    state, one, readings = setup(cell, seed)

    # ---- the window
    i = CHECKED_STEPS
    ends, failed = [], []
    gc.collect()  # set-up's garbage is collected in set-up, and not scanned again in the window
    gc.freeze()
    t0 = time.perf_counter()
    if counter is not None:
        counter.counting = True

    def steps_until(state, i, t_end):
        while True:
            state, loss = one(state, i)
            i += 1
            t = time.perf_counter()
            ends.append(t)
            if not np.isfinite(loss):
                failed.append(i)
            if t >= t_end:
                return state, i

    state, i = steps_until(state, i, t0 + max(seconds - TRACE_S, 0.0) if trace else t0 + seconds)
    tracer = Tracer()
    if trace and ends[-1] < t0 + seconds:
        tracer.start()
        state, i = steps_until(state, i, t0 + seconds)
    window_s = ends[-1] - t0
    gc.unfreeze()
    if counter is not None:
        counter.counting = False
    if tracer.running:
        tracer.stop()
    step_s = np.diff([t0] + ends)
    record = {
        "kind": "train",
        "setup_s": t0 - clock0,
        "window_s": window_s,
        "attempted": len(ends),
        "failed": len(failed),
        "train_tokens": len(ends) * tokens_per_step,
        "step_s_median": float(statistics.median(step_s)),
        "seq": mix["seq"],
        "sizes": cell.sizes,
        "trace": tracer.result or None,
        "memory_peak_bytes": memory_peak_bytes(cell.workload["chips"]),
    }
    b = gen.train_batch(mix, cell.sizes["vocab_size"], seed, 0)
    prog = program_bytes(one.step, shapes_of(state), {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in b.items()})
    slow = sorted(range(len(step_s)), key=lambda k: -step_s[k])[:3]
    say(f"[window] steps={len(ends)} window_s={window_s!r} median_step_s={record['step_s_median']!r} "
        f"slowest={[(k, round(float(step_s[k]), 4)) for k in slow]} "
        f"allocator_peak={record['memory_peak_bytes']} program_bytes={prog}")
    record["memory_peak_bytes"] = max(record["memory_peak_bytes"] or 0, prog)
    del state
    checks = compare(cell, seed, readings)
    return record, checks


# ---------------------------------------------------------------- reference


def reference_steps(cell, seed: int, n: int, precision: str = "f32"):
    """The plain reference through the first ``n`` steps of the cell, from
    the same weights and batches: (losses, clipped first-gradient norm per
    leaf, change of each leaf after ``n`` steps)."""
    import jax
    import jax.numpy as jnp

    sizes, mix, ref = cell.sizes, cell.traffic, cell.reference
    key = seed_key(seed)
    init = jax.jit(lambda k: flatten(ref.init_params(sizes, k)))
    params = jax.jit(lambda k: {p: v.astype(jnp.float32) for p, v in init(k).items()})(key)
    opt = AdamW(cell.config["optimizer"], params)
    loss_g = jax.value_and_grad(lambda p, t, l: ref.nll(unflatten(p), t, l, sizes, precision))
    first = jax.jit(loss_g)
    more = jax.jit(lambda p, acc, t, l: (lambda lg: (lg[0], jax.tree.map(jnp.add, acc, lg[1])))(loss_g(p, t, l)),
                   donate_argnums=(1,))
    scale = jax.jit(lambda g, c: jax.tree.map(lambda x: x / c, g), donate_argnums=(0,))
    losses, grad1 = [], None
    for i in range(n):
        b = gen.train_batch(mix, sizes["vocab_size"], seed, i)
        count = float((b["labels"] >= 0).sum())
        total, grads = first(params, b["tokens"][0], b["labels"][0])
        total = float(total)
        for r in range(1, b["tokens"].shape[0]):
            lr_, grads = more(params, grads, b["tokens"][r], b["labels"][r])
            total += float(lr_)
        grads = scale(grads, count)
        losses.append(total / count)
        params, gn = opt.update(params, grads)
        del grads
        grad1 = gn if grad1 is None else grad1
    p0 = init(key)
    change = {k: float(jnp.linalg.norm((params[k] - p0[k].astype(jnp.float32)).ravel())) for k in sorted(params)}
    return losses, grad1, change


def _leaf_gaps(prog: dict, ref: dict, keys) -> dict:
    """Per leaf: |program's norm - reference's| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def gaps(readings: dict, ref) -> dict:
    """Every number the comparison can hold against a limit, with the leaf
    that set it: ``loss_gap`` (largest relative gap of the checked steps'
    losses), ``loss1_gap`` (the first step's), ``grad_gap`` and
    ``change_gap`` (the worst leaf of the clipped first gradient's norm and
    of the change's), ``grad_gap_median`` and ``change_gap_median`` (the
    median leaf's), and ``grad_gap_ends`` (the worst of the leaves outside
    the stacked layers: embedding, head, final norm, whose gradients sum
    over every position and hold no per-layer vector's rounding noise).
    A cell's limits file names the ones it compares."""
    losses, grad1, change = readings["losses"], readings["grad1"], readings["change"]
    r_loss, r_grad, r_change = ref
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, r_loss)]
    med = statistics.median(r_grad.values())
    moved = [k for k in sorted(r_change) if r_grad[k] >= 1e-3 * med]
    out = {"loss_gap": (max(rel), ""), "loss1_gap": (rel[0], "")}
    grad = _leaf_gaps(grad1, r_grad, sorted(r_grad))
    for name, g in (("grad", grad), ("change", _leaf_gaps(change, r_change, moved))):
        worst = max(g, key=g.get)
        out[f"{name}_gap"] = (g[worst], worst)
        out[f"{name}_gap_median"] = (statistics.median(g.values()), "")
    ends = max((k for k in grad if not k.startswith("blocks/")), key=grad.get)
    out["grad_gap_ends"] = (grad[ends], ends)
    return out


def compare(cell, seed, readings: dict) -> dict:
    t = time.perf_counter()
    ref = reference_steps(cell, seed, len(readings["losses"]))
    found = gaps(readings, ref)
    say(f"[reference] {len(ref[0])} steps in {time.perf_counter() - t:.1f}s; "
        f"losses program={readings['losses']} reference={ref[0]}")
    say("[reference] worst leaves: " + ", ".join(f"{k}={leaf}" for k, (_v, leaf) in found.items() if leaf))
    say("[reference] gaps: " + json.dumps({k: v for k, (v, _leaf) in found.items()}))
    return {k: (found[k][0], lim) for k, lim in cell.limits.items()}
