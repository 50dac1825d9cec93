"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --control-seeds 1,2,3 \
        [--faults half_batch,... [--fault-seeds 1,2,3]] [--seconds 20]

One process on the chip, at the cell's own sizes.  For each seed it prints
one JSON line with the numbers the run compares: the program's (from the
cell's own set-up steps, or for a serving cell a window of ``--seconds``
of the cell's traffic, as a run has) and, for the control seeds, the
control's, which is the reference put in the program's place and computed
in float8 e4m3, the precision below the configurations' bfloat16.  The
lower reading of a number is the largest the program gives over a dozen
seeds or more; the upper is the smallest the control gives.  With
``--faults``, each planted fault of ``chipbench.faults`` is read on the
control seeds too, against the same reference.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_readings(cell, seed: int, control: bool, planted=()) -> dict:
    from chipbench import faults
    from chipbench.drivers import train

    def by_leaf(r):  # each leaf's first-gradient gap, for the look behind a worst-leaf reading
        return train._leaf_gaps(r["grad1"], ref[1], sorted(ref[1]))

    state, _one, readings = train.setup(cell, seed)
    del state
    ref = train.reference_steps(cell, seed, len(readings["losses"]))
    out = {"program": {k: v for k, (v, _l) in train.gaps(readings, ref).items()},
           "grad_by_leaf": {"program": by_leaf(readings)}}
    for kind in planted:
        with faults.planted(kind, serving=False):
            state, _one, broken = train.setup(cell, seed)
            del state
        out[kind] = {k: v for k, (v, _l) in train.gaps(broken, ref).items()}
        out["grad_by_leaf"][kind] = by_leaf(broken)
    if control:
        losses, grad1, change = train.reference_steps(cell, seed, len(readings["losses"]), "fp8")
        low = {"losses": losses, "grad1": grad1, "change": change}
        out["control"] = {k: v for k, (v, _l) in train.gaps(low, ref).items()}
        out["grad_by_leaf"]["control"] = by_leaf(low)
    return out


def serve_readings(cell, seed: int, seconds: float, control: bool, planted=()) -> dict:
    import time

    from chipbench import faults
    from chipbench.drivers import serve_closed, serving

    def widest(done, precision="f32"):
        sample = serving.sample_for_check(done, seed, cell.traffic["check_tokens"])
        return float(max(g.max() for g in serving.reference_gaps(cell, seed, sample, precision)))

    record, done = serve_closed.serve(cell, seed, seconds, False, time.perf_counter())
    out = {"program": {"logit_gap": widest(done)},
           "tokens_per_s": record["serve_tokens"] / record["window_s"]}
    if control:
        out["control"] = {"logit_gap": widest(done, "fp8")}
    for kind in planted:
        with faults.planted(kind, serving=True):
            _record, broken = serve_closed.serve(cell, seed, seconds, False, time.perf_counter())
        out[kind] = {"logit_gap": widest(broken)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="", help="faults of chipbench.faults to plant")
    ap.add_argument("--fault-seeds", default=None, help="seeds to plant them on (default: the control seeds)")
    ap.add_argument("--seconds", type=float, default=20.0, help="a serving cell's window")
    args = ap.parse_args(argv)

    from chipbench.harness import device_info, load_cell
    from chipbench.run import enable_cache

    cell = load_cell(args.workload, False)
    print(json.dumps({"device": device_info(cell.workload["chips"]), "cache": enable_cache()}), flush=True)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    flt = ctl if args.fault_seeds is None else {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        planted = [f for f in args.faults.split(",") if f] if seed in flt else []
        if cell.traffic["driver"] == "train":
            r = train_readings(cell, seed, seed in ctl, planted)
        else:
            r = serve_readings(cell, seed, args.seconds, seed in ctl, planted)
        print(json.dumps({"seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
