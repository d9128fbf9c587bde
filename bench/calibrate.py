"""Readings from which a cell's ``fold_gap`` limit is set.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--rounds 2]

For each seed, in one process: build the cell's pool, serve each pool
round once (set-up), then ``--rounds`` more rounds through the same
entry as a run's window, and compare them with the plain reference as
a run does (the program's reading).  Then the control: the reference
computed one precision step lower (``round_reference(high=True)``),
chained over the same rounds and compared in the same way.  A limit
lies above every program reading and below every control reading.
Prints one JSON line per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def readings(cell, seed: int, rounds: int, require_chip: bool = True):
    import jax
    harness.enable_cache()
    if require_chip:
        harness.check_devices(cell.workload["chips"])
    cfg = harness.engine_config(cell.config)
    g0, pool = harness.build_pool(seed, cell, cfg.n_slots)
    g = jax.numpy.asarray(g0)
    warm = list(range(len(pool)))
    for p in warm:
        g = harness.serve(cfg, g, pool[p]).new_global
    seq, results, times = [], [], []
    for i in range(rounds):
        t = time.perf_counter()
        res = harness.serve(cfg, g, pool[i % len(pool)])
        times.append(time.perf_counter() - t)
        seq.append(i % len(pool))
        results.append(res)
        g = res.new_global
    win = harness.Window(seq, results, times, sum(times), {})
    program = harness.check(cell, cfg, g0, pool, warm, win)["worst"]
    ref = harness.load_module("references", cell.config["reference"])
    exact = [ref.round_reference(r.payloads, r.scales, r.accepted)
             for r in pool]
    low = [ref.round_reference(r.payloads, r.scales, r.accepted, high=True)
           for r in pool]
    want = list(ref.chain(exact, g0, warm + seq, cfg.n_params))
    ctrl = list(ref.chain(low, g0, warm + seq, cfg.n_params))
    # the control's globals are what a float32 server would publish
    control = max(ref.gap(c[0].astype(np.float32), v, s)
                  for c, (v, s) in zip(ctrl[len(warm):], want[len(warm):]))
    return dict(seed=seed, program=program, control_fold_gap=control,
                round_s=times)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            line = readings(cell, seed, args.rounds)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 1
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
