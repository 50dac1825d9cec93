"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds, for
the rehearsals and the checks of the comparison.  Widths shrink here and
only here: the chip runs the configurations as their files state them."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.harness import load_cell  # noqa: E402

# The model width stays at the configurations' own (1024, 3840 is cut to
# 1024): the compared numbers scale with it (logits with the embedding's
# dot products, rounding with the weights' magnitude), so the cells'
# limits hold at these sizes too.  Depth, vocabulary and heads shrink.
SIZES = {
    "ssm": dict(n_layers=4, d_model=1024, vocab_size=4000, padded_vocab=4096,
                ssm_state=32, ssm_head_dim=64, ssm_chunk=16),
    "dense": dict(n_layers=2, d_model=1024, n_heads=4, n_kv_heads=2, head_dim=120,
                  d_ff=2048, vocab_size=512, padded_vocab=512, sliding_window=32),
}
TRAFFIC = {
    "train": dict(batch=8, seq=64),
    "serve_closed": dict(batch=4, max_len=96, requests_per_call=8,
                         prompt_len={"median": 16, "sigma": 0.8, "buckets": [8, 16, 32]},
                         output_len={"median": 6, "sigma": 0.8, "min": 2, "max": 24},
                         check_tokens=40),
}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}  # peaks of the chip the cells target


def tiny_cell(name: str, trace: bool = False):
    cell = load_cell(name, trace)
    cell.config = copy.deepcopy(cell.config)
    cell.config["config"].update(SIZES[cell.config["config"]["family"]])
    cell.traffic = {**cell.traffic, **TRAFFIC[cell.traffic["driver"]]}
    return cell
