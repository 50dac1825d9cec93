"""Trace-time optimization flags for §Perf iterations.

Set by launch/dryrun.py (--opt a,b,c) before lowering; read by model code
at trace time.  Flags:

  sp           — sequence-parallel residual stream (model.py)
  mamba_heads  — shard SSD head dim over `model` inside the mamba mixer
  batch_axes   — mesh axes the batch dim is sharded over (set automatically)
"""
from __future__ import annotations

_FLAGS = {
    "sp": False,
    "mamba_heads": False,
    "moe_a2a": False,  # shard_map local-dispatch MoE (§Perf iteration 3)
    "sp_sub": False,  # per-sublayer resharding (REFUTED, kept for ablation)
    "batch_axes": None,
    "mesh": None,
}


def set_flags(**kw) -> None:
    for k, v in kw.items():
        if k not in _FLAGS:
            raise KeyError(k)
        _FLAGS[k] = v


def reset() -> None:
    set_flags(sp=False, mamba_heads=False, moe_a2a=False,
              sp_sub=False, batch_axes=None, mesh=None)


def get(name: str):
    return _FLAGS[name]
