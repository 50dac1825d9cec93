"""Bring-up run of the data plane on one TPU chip.

    python chip_smoke.py

Drives the system's own entry points once at published widths, with
random weights and data made from fixed seeds:

* train  — ``repro.launch.train.train_loop`` on mamba2-370m (48 layers,
  d_model 1024, vocab 50280), batch 8 x 2048, six AdamW steps;
* serve  — ``ServeEngine`` on mamba2-370m: 12 greedy requests through an
  8-row batch (so rows are refilled), plus request 0 served alone;
* attention — the jitted ``Model.prefill`` of h2o-danube-3-4b on 1 x 2048
  tokens (attention, GQA and MLP at full width);
* kernels — each Pallas kernel compiled for the chip against its jnp
  reference in ``repro.kernels.ref``;
* serve curve — ``repro.serve.latency.calibrate`` on the serve engine.

Each phase prints its findings on a line of its own; the last line is one
JSON object naming the device.  A failed check raises, so the exit code is
non-zero.  A run whose first JAX device is not a TPU exits non-zero before
any phase: there is no CPU fallback.
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.serve.latency import calibrate  # noqa: E402

SEED = 0


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def check_device() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind})"
        )
    return dev


def phase_train(
    arch: str = "mamba2-370m",
    reduced: bool = False,
    batch: int = 8,
    seq: int = 2048,
    steps: int = 6,
) -> dict:
    res = train_loop(
        arch, steps=steps, batch=batch, seq=seq, reduced=reduced,
        seed=SEED, log_every=1,
    )
    first, last = res["first_loss"], res["last_loss"]
    _require(
        np.isfinite(first) and np.isfinite(last),
        f"train losses finite (first {first}, last {last})",
    )
    times = res["step_times"]
    print(
        f"[train] {arch} batch={batch} seq={seq} steps={steps} "
        f"first_loss={first!r} last_loss={last!r} "
        f"first_step_s={times[0]!r} (compile + one step) "
        f"median_later_step_s={statistics.median(times[1:])!r}"
    )
    return res


def phase_serve(
    cfg,
    n_requests: int = 12,
    batch_size: int = 8,
    max_len: int = 1024,
    prompt_lens: tuple = (128, 256),
    new_tokens: int = 32,
) -> ServeEngine:
    params = jax.jit(Model(cfg).init)(jax.random.PRNGKey(SEED))
    engine = ServeEngine(
        cfg, params, max_len=max_len, batch_size=batch_size, seed=SEED
    )
    rng = np.random.default_rng(SEED)
    prompts = [
        rng.integers(0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)])
        .tolist()
        for i in range(n_requests)
    ]

    def requests():
        return [
            Request(i, p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)
        ]

    out = engine.generate(requests())  # compiles prefill per length + decode
    for rid, ids in out.items():
        _require(
            len(ids) == new_tokens,
            f"request {rid} got {len(ids)} tokens, budget {new_tokens}",
        )
        _require(
            all(0 <= t < cfg.vocab_size for t in ids),
            f"request {rid} has ids outside [0, {cfg.vocab_size})",
        )
    solo = engine.generate([Request(0, prompts[0], max_new_tokens=new_tokens)])
    _require(
        solo[0][0] == out[0][0],
        f"request 0 first token alone {solo[0][0]} != batched {out[0][0]}",
    )

    # Warm rerun with every decode step timed to its end on the device.
    inner = engine._decode
    decode_s, decode_steps = 0.0, 0

    def timed_decode(*args):
        nonlocal decode_s, decode_steps
        t0 = time.perf_counter()
        res = jax.block_until_ready(inner(*args))
        decode_s += time.perf_counter() - t0
        decode_steps += 1
        return res

    engine._decode = timed_decode
    t0 = time.perf_counter()
    warm = engine.generate(requests())
    wall_s = time.perf_counter() - t0
    engine._decode = inner
    _require(warm == out, "warm rerun differs from the first greedy run")
    # the first token of each request comes from its prefill
    decode_tokens = n_requests * (new_tokens - 1)
    print(
        f"[serve] {cfg.name} requests={n_requests} batch={batch_size} "
        f"max_len={max_len} prompt_lens={list(prompt_lens)} "
        f"new_tokens={new_tokens} budgets_met=True "
        f"solo_first_token={solo[0][0]} batched_first_token={out[0][0]} "
        f"decode_steps={decode_steps} decode_s={decode_s!r} "
        f"decode_tokens_per_s={decode_tokens / decode_s!r} "
        f"generate_wall_s={wall_s!r}"
    )
    return engine


def phase_serve_curve(
    engine: ServeEngine,
    device_kind: str,
    batch_sizes: tuple = (1, 8, 32, 128),
    steps: int = 24,
) -> None:
    m = calibrate(engine, batch_sizes=batch_sizes, steps=steps)
    print(
        f"[serve_curve] device_kind={device_kind!r} {engine.cfg.name} "
        f"batch_sizes={list(batch_sizes)} base_s={m.base!r} "
        f"per_req_s={m.per_req!r}"
    )


def phase_prefill(cfg, batch: int = 1, seq: int = 2048) -> None:
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    tokens = jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (batch, seq), 0, cfg.vocab_size,
        jnp.int32,
    )
    prefill = jax.jit(model.prefill)
    times = []
    for _ in range(2):  # the first call compiles
        t0 = time.perf_counter()
        logits, _ = prefill(params, {"tokens": tokens})
        logits.block_until_ready()
        times.append(time.perf_counter() - t0)
    _require(
        logits.shape == (batch, 1, cfg.padded_vocab),
        f"prefill logits shape {logits.shape}",
    )
    _require(
        bool(jnp.isfinite(logits.astype(jnp.float32)).all()),
        "prefill logits finite",
    )
    print(
        f"[attention] {cfg.name} prefill batch={batch} seq={seq} "
        f"logits_shape={tuple(logits.shape)} finite=True "
        f"first_call_s={times[0]!r} (compile + run) warm_s={times[1]!r}"
    )


def _check_close(name: str, got, want, tol: float) -> None:
    """``np.testing.assert_allclose(atol=tol, rtol=tol)``'s condition;
    prints the max absolute error."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    ok = bool(np.all(err <= tol + tol * np.abs(want)))
    print(
        f"[kernels] {name} max_abs_err={float(err.max())!r} tol={tol} "
        f"within_tol={ok}"
    )
    _require(ok, f"{name} within {tol} of its reference")


def _on_host(fn, *arrays):
    """Evaluate a reference on the host's CPU backend, where the tests'
    tolerances were set: its f32 exp is exact to about an ulp, while the
    TPU's is off by up to ~6e-6 relative, which the SSD reference's 4096
    sequential decay steps compound past the tolerance."""
    return jax.jit(fn)(*jax.device_put(arrays, jax.devices("cpu")[0]))


def phase_kernels(
    flash: tuple = (1, 4096, 32, 8, 128),  # B, S=T, H, G, K
    ssd: tuple = (1, 4096, 32, 64, 128),  # B, S, H, P, N
    norm_dims: tuple = (1024, 6144),
    norm_rows: int = 4096,
) -> None:
    """Each kernel against ``repro.kernels.ref`` at the tolerances of
    tests/test_kernels_*.py."""
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    B, S, H, G, K = flash
    bf16 = jnp.bfloat16
    q = jax.random.normal(next(keys), (B, S, H, K)).astype(bf16)
    k = jax.random.normal(next(keys), (B, S, G, K)).astype(bf16)
    v = jax.random.normal(next(keys), (B, S, G, K)).astype(bf16)
    pos = jnp.arange(S, dtype=jnp.int32)
    got = jax.jit(ops.flash_attention, static_argnums=(5, 6))(
        q, k, v, pos, pos, True, None
    )
    want = _on_host(
        lambda q, k, v, p: ref.flash_attention_ref(q, k, v, p, p, True, None),
        q, k, v, pos,
    )
    _check_close(
        f"flash_attention B{B} S=T={S} H{H} G{G} K{K} bf16 causal",
        got, want, 2e-2,
    )
    del q, k, v, got, want

    B, S, H, P, N = ssd
    x = jax.random.normal(next(keys), (B, S, H, P))
    dt = jax.random.uniform(next(keys), (B, S, H), minval=0.001, maxval=0.1)
    A = -jax.random.uniform(next(keys), (H,), minval=0.5, maxval=4.0)
    Bm = jax.random.normal(next(keys), (B, S, N))
    Cm = jax.random.normal(next(keys), (B, S, N))
    y, st = jax.jit(ops.ssd_scan)(x, dt, A, Bm, Cm)
    y_ref, st_ref = _on_host(ref.ssd_scan_ref, x, dt, A, Bm, Cm)
    name = f"ssd_scan B{B} S{S} H{H} P{P} N{N} f32"
    _check_close(f"{name} y", y, y_ref, 2e-4)
    _check_close(f"{name} final_state", st, st_ref, 2e-4)

    for D in norm_dims:
        xn = jax.random.normal(next(keys), (norm_rows, D))
        scale = jax.random.normal(next(keys), (D,))
        _check_close(
            f"rmsnorm rows={norm_rows} D={D} f32",
            jax.jit(ops.rmsnorm)(xn, scale),
            _on_host(ref.rmsnorm_ref, xn, scale),
            1e-5,
        )


def main() -> int:
    dev = check_device()
    cache = enable_compile_cache()
    count = len(jax.devices())
    print(
        f"[device] platform={dev.platform} kind={dev.device_kind!r} "
        f"count={count} compile_cache={cache}"
    )
    phase_train()
    engine = phase_serve(get_config("mamba2-370m"))
    phase_serve_curve(engine, dev.device_kind)
    del engine
    phase_prefill(get_config("h2o-danube-3-4b"))
    phase_kernels()
    cache_dir = Path(cache)
    n_entries = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"[cache] {cache} entries={n_entries}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": count,
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
