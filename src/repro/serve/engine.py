"""Batched serving engine: continuous prefill + decode over a request queue.

Small-scale (CPU-runnable) but structured like a production server: a
fixed-width decode batch is continuously refilled from a pending-request
queue — each incoming request is prefilled *solo* (exact prompt length, no
padding), its KV cache scattered into a free batch row, and the decode
loop samples every live row per step, retiring rows on EOS/max-tokens and
refilling them from the queue.

Correctness properties (tests/test_serve_batched.py):

* **Batch isolation** — a request's greedy output is bit-identical whether
  it is served alone or batched with arbitrary batch-mates.  Solo prefill
  assigns true positions ``0..len(prompt)-1`` (no pad tokens ever enter a
  cache), and decode runs with *per-row* positions (`Model.decode_step`
  with a ``[B]`` pos vector): each row attends only over its own written
  slots — other rows' writes land at strictly higher slots, blocked by the
  causal mask, and contribute exactly-0.0 softmax probabilities.
* **Budget validation** — ``len(prompt) + max_new_tokens`` over
  ``max_len`` raises up front (default) or explicitly marks the request
  ``truncated`` (``overflow="truncate"``), never a silently short answer.
* **EOS exclusion** — a sampled EOS terminates the request and is *not*
  included in ``generated``.

The per-row position path needs the slot == position invariant, so the
engine rejects sliding-window (ring-buffer) configs at construction.

Each phase of ``generate`` runs inside a ``repro.serve.*`` host span
(``jax.profiler.TraceAnnotation``), so that a profiler trace says what the
host was doing while the device waited: ``refill`` per refilled row, with
``prefill`` and ``insert_row`` inside it, then per step ``sample``,
``decode`` (dispatch) and ``fetch`` (the logits' copy to the host).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ArchConfig
from ..models.model import Model


@dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    generated: List[int] = field(default_factory=list)
    done: bool = False
    truncated: bool = False  # budget was capped (overflow="truncate")


class ServeEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        params,
        max_len: int = 512,
        eos_id: Optional[int] = None,
        seed: int = 0,
        batch_size: int = 8,
        overflow: str = "error",  # or "truncate"
    ):
        if cfg.sliding_window is not None:
            raise NotImplementedError(
                "ServeEngine's per-row decode positions require "
                "sliding_window=None (ring wrap breaks the slot == "
                "position invariant)"
            )
        if overflow not in ("error", "truncate"):
            raise ValueError(
                f"overflow must be 'error' or 'truncate', got {overflow!r}"
            )
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self.batch_size = batch_size
        self.overflow = overflow
        self._rng = np.random.default_rng(seed)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        logits = np.asarray(logits, dtype=np.float64)
        logits[self.cfg.vocab_size :] = -1e30  # mask padded vocab
        if temperature <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / temperature)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _cache_dtype(self):
        return (
            jnp.float32 if self.cfg.dtype == "float32" else jnp.bfloat16
        )

    def _budget(self, r: Request) -> int:
        """Validated per-request token budget (satellite: no silent
        truncation).  Raises on over-budget requests unless the engine was
        built with ``overflow="truncate"``, which caps the budget and
        marks the request."""
        if not r.prompt:
            raise ValueError(f"request {r.request_id}: empty prompt")
        if r.max_new_tokens < 1:
            raise ValueError(
                f"request {r.request_id}: max_new_tokens must be >= 1"
            )
        if len(r.prompt) >= self.max_len:
            raise ValueError(
                f"request {r.request_id}: prompt length {len(r.prompt)} "
                f"leaves no room to generate within max_len={self.max_len}"
            )
        budget = r.max_new_tokens
        if len(r.prompt) + budget > self.max_len:
            if self.overflow == "error":
                raise ValueError(
                    f"request {r.request_id}: prompt ({len(r.prompt)}) + "
                    f"max_new_tokens ({budget}) exceeds "
                    f"max_len={self.max_len}; shorten the request or build "
                    f"the engine with overflow='truncate'"
                )
            budget = self.max_len - len(r.prompt)
            r.truncated = True
        return budget

    def _insert_row(self, cache, row_cache, row: int):
        """Scatter a solo-prefilled (B=1) cache into batch row ``row``.

        k/v and mamba leaves carry ``[n_blocks, B, ...]`` — the whole row
        is replaced, clearing any previous occupant.  The shared attention
        ``pos`` leaf ([n_blocks, 1, W]) merges by max: values are
        slot-index-or--1, and every row writes position == slot.
        """

        def merge(path, b, r):
            if getattr(path[-1], "key", None) == "pos":
                return jnp.maximum(b, r)
            return b.at[:, row].set(r[:, 0])

        return jax.tree_util.tree_map_with_path(merge, cache, row_cache)

    def generate(
        self, requests: List[Request], batch_size: Optional[int] = None
    ) -> Dict[int, List[int]]:
        """Serve requests to completion with continuous batch refill."""
        if not requests:
            return {}
        budgets = {i: self._budget(r) for i, r in enumerate(requests)}
        pending = deque(range(len(requests)))
        B = max(1, min(batch_size or self.batch_size, len(requests)))
        dt = self._cache_dtype()
        cache = self.model.init_cache(B, self.max_len, dtype=dt)
        row_req: List[Optional[int]] = [None] * B  # request index per row
        row_pos = np.zeros(B, dtype=np.int64)  # next write position
        tok = np.zeros((B, 1), dtype=np.int32)
        last: List[Optional[np.ndarray]] = [None] * B

        while True:
            # Refill retired/empty rows: solo prefill (exact length, true
            # positions — the padding/position-leakage fix), then scatter
            # the row cache into the batch.
            for b in range(B):
                if row_req[b] is None and pending:
                    with TraceAnnotation("repro.serve.refill"):
                        ri = pending.popleft()
                        r = requests[ri]
                        with TraceAnnotation("repro.serve.prefill"):
                            logits, row_cache = self._prefill(
                                self.params,
                                {"tokens": jnp.asarray([r.prompt], jnp.int32)},
                                self.model.init_cache(1, self.max_len, dtype=dt),
                            )
                        with TraceAnnotation("repro.serve.insert_row"):
                            cache = self._insert_row(cache, row_cache, b)
                        last[b] = np.asarray(logits)[0, 0]
                        row_req[b] = ri
                        row_pos[b] = len(r.prompt)
            live = [b for b in range(B) if row_req[b] is not None]
            if not live:
                break

            with TraceAnnotation("repro.serve.sample"):
                for b in live:
                    ri = row_req[b]
                    r = requests[ri]
                    t = self._sample(last[b], r.temperature)
                    if self.eos_id is not None and t == self.eos_id:
                        r.done = True  # EOS consumed, not returned
                        row_req[b] = None
                        continue
                    r.generated.append(t)
                    tok[b, 0] = t
                    if len(r.generated) >= budgets[ri]:
                        r.done = True
                        row_req[b] = None

            if all(ri is None for ri in row_req) and not pending:
                break
            # Retired rows ride along as dummies (their stale token at a
            # clamped position): writes stay confined to their own cache
            # row and are replaced wholesale on refill.
            with TraceAnnotation("repro.serve.decode"):
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(tok),
                    jnp.asarray(
                        np.minimum(row_pos, self.max_len - 1), jnp.int32
                    ),
                )
            with TraceAnnotation("repro.serve.fetch"):
                arr = np.asarray(logits)[:, 0, :]
            for b in range(B):
                if row_req[b] is not None:
                    last[b] = arr[b]
                    row_pos[b] += 1
        return {r.request_id: r.generated for r in requests}
