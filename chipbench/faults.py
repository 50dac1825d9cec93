"""Faults planted in the program underneath a run, to show that the output
check catches them (``tests/test_faults.py`` at a tiny size, ``control.py``
on the chip at the cells' sizes).  Each patches the program's own module
attribute, so the harness drives it exactly as it drives the sound path.

Training: ``unchanged_state`` (a step that returns its state unchanged),
``half_batch`` (half of the rows left out of the loss, the mean taken over
the rest; half of the positions where the batch has one row),
``leaf_dropped`` (one leaf's update left out).  Serving:
``unchanged_state`` (a decode step that returns its cache unchanged),
``half_batch`` (half of the rows computed, their logits copied to the
rest), ``token_altered`` (every seventh sampled token replaced by the next
id).  The one-chip cells have no exchange between chips to leave out.
"""
from __future__ import annotations

from contextlib import contextmanager

TRAIN = ("unchanged_state", "half_batch", "leaf_dropped")
SERVE = ("unchanged_state", "half_batch", "token_altered")


@contextmanager
def planted(kind: str, serving: bool):
    """Plant one fault for the duration of the block."""
    if serving:
        undo = _serve(kind)
    else:
        undo = _train(kind)
    try:
        yield
    finally:
        undo()


def _train(kind: str):
    from repro.train import train_step as ts

    if kind not in TRAIN:
        raise ValueError(f"unknown training fault {kind!r}")
    real = ts.make_train_step

    def broken(model, opt_cfg, **kw):
        step = real(model, opt_cfg, **kw)

        def f(state, batch):
            if kind == "half_batch":
                lab = batch["labels"]
                if lab.shape[0] > 1:
                    lab = lab.at[lab.shape[0] // 2:].set(-1)
                else:
                    lab = lab.at[:, lab.shape[1] // 2:].set(-1)
                batch = {**batch, "labels": lab}
            new, metrics = step(state, batch)
            if kind == "unchanged_state":
                return state, metrics
            if kind == "leaf_dropped":
                sub = dict(new.params["blocks"]["sub0"])
                first = sorted(sub)[0]
                sub[first] = state.params["blocks"]["sub0"][first]
                params = {**new.params, "blocks": {**new.params["blocks"], "sub0": sub}}
                return new._replace(params=params), metrics
            return new, metrics

        return f

    ts.make_train_step = broken

    def undo():
        ts.make_train_step = real

    return undo


def _serve(kind: str):
    import jax.numpy as jnp
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    if kind not in SERVE:
        raise ValueError(f"unknown serving fault {kind!r}")
    if kind == "token_altered":
        real = ServeEngine._sample
        calls = [0]

        def sample(self, logits, temperature):
            calls[0] += 1
            t = real(self, logits, temperature)
            return (t + 1) % self.cfg.vocab_size if calls[0] % 7 == 0 else t

        ServeEngine._sample = sample

        def undo():
            ServeEngine._sample = real

        return undo

    real = Model.decode_step

    def decode_step(self, params, cache, tokens, pos):
        logits, new = real(self, params, cache, tokens, pos)
        if kind == "unchanged_state":
            return logits, cache
        half = tokens.shape[0] // 2 or 1
        return logits[jnp.arange(tokens.shape[0]) % half], new

    Model.decode_step = decode_step

    def undo():
        Model.decode_step = real

    return undo
