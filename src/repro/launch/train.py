"""End-to-end training driver with checkpoint/restart + fault tolerance.

CPU-runnable (reduced configs) and mesh-ready (full configs on TPU):

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Features exercised: AdamW + cosine schedule, grad clip, microbatching,
async checkpointing every --ckpt-every steps, automatic resume from the
latest complete checkpoint, simulated failure injection (--fail-at) that
kills and restarts the loop mid-run to prove restartability, and
straggler detection hooks.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced_config
from ..models.model import Model, n_params
from ..train import checkpoint
from ..train.data import DataLoader
from ..train.fault_tolerance import StragglerDetector
from ..train.optimizer import AdamWConfig
from ..train.train_step import init_train_state, make_train_step
from .compile_cache import enable_compile_cache


def train_loop(
    arch: str,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    reduced: bool = True,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    microbatches: int = 1,
    fail_at: int | None = None,
    seed: int = 0,
    log_every: int = 10,
    lr: float = 3e-4,
) -> dict:
    cfg = reduced_config(arch) if reduced else get_config(arch)
    model = Model(cfg)
    opt_cfg = AdamWConfig(lr_peak=lr, warmup_steps=min(20, steps // 5 + 1),
                          total_steps=steps)
    step_fn = jax.jit(
        make_train_step(model, opt_cfg, num_microbatches=microbatches),
        donate_argnums=(0,),
    )
    loader = DataLoader(cfg, batch, seq, seed=seed)

    start_step = 0
    state = None
    writer = None
    if ckpt_dir:
        writer = checkpoint.AsyncWriter(ckpt_dir, keep=2)
        last = checkpoint.latest_step(ckpt_dir)
        if last is not None:
            template = jax.eval_shape(
                lambda k: init_train_state(model, k), jax.random.PRNGKey(seed)
            )
            state, meta = checkpoint.restore(ckpt_dir, template)
            start_step = meta["step"]
            loader.restore(meta["loader"])
            print(f"[resume] restored step {start_step} from {ckpt_dir}")
    if state is None:
        state = init_train_state(model, jax.random.PRNGKey(seed))
    print(
        f"[train] {cfg.name} ({'reduced' if reduced else 'full'}) "
        f"params={n_params(state.params):,} steps={steps}"
    )

    stragglers = StragglerDetector()
    losses = []
    step_times = []
    for step in range(start_step, steps):
        batch_np = loader.next()
        t0 = time.perf_counter()
        state, metrics = step_fn(
            state, jax.tree.map(jnp.asarray, batch_np)
        )
        loss = float(metrics["loss"])  # waits for the step on the device
        dt = time.perf_counter() - t0
        stragglers.record(host=0, step_time=dt)
        losses.append(loss)
        step_times.append(dt)
        if step % log_every == 0 or step == steps - 1:
            print(
                f"  step {step:5d} loss {loss:8.4f} "
                f"gnorm {float(metrics['grad_norm']):8.3f} "
                f"lr {float(metrics['lr']):.2e} ({dt*1e3:.0f} ms)"
            )
        if writer and (step + 1) % ckpt_every == 0:
            writer.submit(
                step + 1, state, {"loader": loader.state()}
            )
        if fail_at is not None and step + 1 == fail_at:
            if writer:
                writer.close()
            raise RuntimeError(f"injected failure at step {fail_at}")
    if writer:
        writer.submit(steps, state, {"loader": loader.state()})
        writer.close()
    return {
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "final_step": steps,
        # seconds per step, first one including its compile
        "step_times": step_times,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    res = train_loop(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        reduced=args.reduced, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, microbatches=args.microbatches,
        fail_at=args.fail_at, seed=args.seed,
    )
    print(f"[done] {res}")


if __name__ == "__main__":
    main()
