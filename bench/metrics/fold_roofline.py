"""The fold's share of its roofline: the least time the chip needs for
the fold's algorithmic work, over ``fold_s``.

The work is counted from the round's accepted packets and shapes, never
from the kernel's grid, so it reads the same whatever implements the
fold: each accepted payload read once at wire width, with its slot
index and weight (and q8 scale) once, and the (S, W) f32 accumulator
and the (S,) counts read and written once per round.  FLOPs are 2 * W
per packet (a multiply by the weight and an add), far below the compute
bound, so bytes bound it.
"""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_fold_s", Path(__file__).with_name("fold_s.py"))
fold_s = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fold_s)


def fold_bytes(accepted: int, payload: int, wire_itemsize: int, q8: bool,
               n_slots: int) -> int:
    per_packet = payload * wire_itemsize + 4 + 4 + (4 if q8 else 0)
    state = 2 * (n_slots * payload * 4 + n_slots * 4)
    return accepted * per_packet + state


def fold_flops(accepted: int, payload: int) -> int:
    return 2 * payload * accepted


def least_seconds(rnd: dict, peaks: dict) -> float:
    b = fold_bytes(rnd["accepted"], rnd["payload"], rnd["wire_itemsize"],
                   rnd["q8"], rnd["n_slots"])
    f = fold_flops(rnd["accepted"], rnd["payload"])
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])


def read(obs):
    measured = fold_s.read(obs)
    if not measured:
        return None
    least = sum(least_seconds(r, obs.peaks) for r in obs.rounds)
    return 100.0 * least / obs.n_rounds / measured
