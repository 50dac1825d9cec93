"""Serving pieces that the backlog driver and ``control.py`` share: the
engine built on the benchmark's weights, warm-up of exactly the window's
shapes, the window and token timestamps, the step wrappers, and the
output check.

A token is "on the host" when ``ServeEngine`` appends it to its request's
``generated`` list; the benchmark hands the engine lists that stamp each
append with the host clock, and changes nothing else in the engine.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import gen
from chipbench.harness import arch_config, say
from chipbench.reference import seed_key


class WindowClosed(Exception):
    """Raised inside ``generate`` at the first token after the window."""


class Window:
    """The measured window: it opens at the first decode step (every row
    of the batch then holds a request) and lasts ``seconds``."""

    def __init__(self, seconds: float, on_open=None):
        self.seconds = seconds
        self.on_open = on_open
        self.t0: float | None = None
        self.t_end: float | None = None

    def open(self, now: float) -> None:
        if self.t0 is None:
            self.t0, self.t_end = now, now + self.seconds
            if self.on_open is not None:
                self.on_open()


class StampedList(list):
    """A list that records the host clock at every append, and ends the
    ``generate`` call that appends to it once its window has closed."""

    def __init__(self, window: Window | None = None):
        super().__init__()
        self.times: list[float] = []
        self.window = window

    def append(self, item) -> None:
        t = time.perf_counter()
        w = self.window
        if w is not None and w.t_end is not None and t > w.t_end:
            raise WindowClosed
        self.times.append(t)
        super().append(item)


def build(cell, seed: int):
    import jax
    from repro.serve.engine import ServeEngine

    cfg = arch_config(cell.config)
    mix = cell.traffic
    params = jax.block_until_ready(jax.jit(lambda k: cell.reference.init_params(cell.sizes, k))(seed_key(seed)))
    engine = ServeEngine(cfg, params, max_len=mix["max_len"], batch_size=mix["batch"], seed=seed)
    return engine


def requests(reqs: list, window: Window | None = None) -> list:
    from repro.serve.engine import Request

    return [
        Request(r.index, r.prompt, max_new_tokens=r.max_new_tokens, generated=StampedList(window))
        for r in reqs
    ]


def warm_up(engine, mix: dict) -> None:
    """Every program a window runs, once, through the engine's own
    callables: the empty caches of one row and of the batch, the solo
    prefill at each prompt length, the row scatter into each row and the
    decode step at the mix's batch."""
    import jax
    import jax.numpy as jnp

    B, dt = mix["batch"], engine._cache_dtype()
    cache = engine.model.init_cache(B, engine.max_len, dtype=dt)
    for n in mix["prompt_len"]["buckets"]:
        logits, row = engine._prefill(
            engine.params, {"tokens": jnp.asarray([[1] * n], jnp.int32)},  # as generate makes it
            engine.model.init_cache(1, engine.max_len, dtype=dt),
        )
        np.asarray(logits)
    for r in range(B):  # the scatter is compiled for each row
        cache = engine._insert_row(cache, row, r)
    pos = jnp.asarray(np.full(B, engine.max_len - 1), jnp.int32)
    logits, cache = engine._decode(engine.params, cache, jnp.asarray(np.zeros((B, 1), np.int32)), pos)
    np.asarray(logits)
    jax.block_until_ready(cache)


class StepHooks:
    """Wraps ``engine._prefill`` and ``engine._decode``: the first decode
    step opens the window, each decode step's start is kept, each call runs
    inside a ``cb.prefill`` / ``cb.decode`` host span, and ``on_decode``
    runs before each decode step.  With ``synced`` (traced runs) each call ends in
    ``block_until_ready`` (the engine reads every result on the host at
    once, so this adds no synchronisation it lacks) and decode steps are
    timed."""

    def __init__(self, engine, window: Window, synced: bool, on_decode=None):
        import jax

        self.decode: list[tuple[int, float, float]] = []  # (rows, start, seconds)
        self.starts: list[float] = []  # each decode step's start
        inner_p, inner_d = engine._prefill, engine._decode
        self.jitted = {"prefill": inner_p, "decode": inner_d}
        sync = jax.block_until_ready if synced else (lambda x: x)

        def prefill(params, batch, cache):
            with jax.profiler.TraceAnnotation("cb.prefill"):
                return sync(inner_p(params, batch, cache))

        def decode(params, cache, tokens, pos):
            t = time.perf_counter()
            window.open(t)
            self.starts.append(t)
            if on_decode is not None:
                on_decode(t)
            with jax.profiler.TraceAnnotation("cb.decode"):
                out = sync(inner_d(params, cache, tokens, pos))
            if synced:
                self.decode.append((tokens.shape[0], t, time.perf_counter() - t))
            return out

        engine._prefill, engine._decode = prefill, decode


def footprint(engine, hooks: StepHooks, mix: dict) -> int:
    """The larger of the bytes that the decode step at the mix's batch and
    the solo prefill at its longest prompt hold while they run."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import program_bytes, shapes_of

    dt, L = engine._cache_dtype(), engine.max_len
    params = shapes_of(engine.params)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    B = mix["batch"]
    cache = jax.eval_shape(lambda: engine.model.init_cache(B, L, dtype=dt))
    one = jax.eval_shape(lambda: engine.model.init_cache(1, L, dtype=dt))
    return max(
        program_bytes(hooks.jitted["decode"], params, cache, i32(B, 1), i32(B)),
        program_bytes(hooks.jitted["prefill"], params, {"tokens": i32(1, max(mix["prompt_len"]["buckets"]))}, one),
    )


def sample_for_check(done: list, seed: int, min_tokens: int) -> list:
    """The longest finished request and others drawn from the seed until
    the sample holds ``min_tokens`` served tokens."""
    order = gen.rng(seed, 4).permutation(len(done))
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt) + len(done[i].generated))
    picked, n = [longest], len(done[longest].generated)
    for i in order:
        if n >= min_tokens:
            break
        if i != longest:
            picked.append(int(i))
            n += len(done[i].generated)
    return [done[i] for i in picked]


def reference_gaps(cell, seed: int, sample: list, precision: str = "f32") -> list[np.ndarray]:
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best (over the real
    vocabulary).  With ``precision="fp8"`` (the control), the served token
    is replaced at each position by the one the fp8 reference puts first,
    and its gap is read in the float32 reference."""
    import jax
    import jax.numpy as jnp

    sizes, ref = cell.sizes, cell.reference
    V = sizes["vocab_size"]
    params = jax.jit(lambda k: jax.tree.map(lambda a: a.astype(jnp.float32),
                                            ref.init_params(sizes, k)))(seed_key(seed))
    f32 = jax.jit(lambda p, t: ref.logits(p, t, sizes, "f32")[:, :V])
    low = jax.jit(lambda p, t: jnp.argmax(ref.logits(p, t, sizes, precision)[:, :V], axis=-1))
    out = []
    for r in sample:
        seq = list(r.prompt) + list(r.generated[:-1])
        n = len(seq)
        padded = np.zeros(cell.traffic["max_len"], np.int32)  # one shape: one compile
        padded[:n] = seq
        lg = np.asarray(f32(params, padded))
        at = np.arange(len(r.prompt) - 1, n)
        served = np.asarray(r.generated)
        if precision != "f32":
            served = np.asarray(low(params, padded))[at]
        out.append(lg[at].max(axis=-1) - lg[at, served])
    return out


def check(cell, seed: int, done: list, precision: str = "f32") -> dict:
    t = time.perf_counter()
    sample = sample_for_check(done, seed, cell.traffic["check_tokens"])
    gaps = reference_gaps(cell, seed, sample, precision)
    widest = float(max(g.max() for g in gaps))
    say(f"[reference] {len(sample)} requests, {sum(len(g) for g in gaps)} served tokens, "
        f"{time.perf_counter() - t:.1f}s; widest logit gap {widest!r}")
    return {"logit_gap": (widest, cell.limits["logit_gap"])}


def budget_misses(reqs: list) -> int:
    """Requests that did not get exactly their token budget."""
    return sum(1 for r in reqs if len(r.generated) != r.max_new_tokens)
