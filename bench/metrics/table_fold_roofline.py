"""The table fold's share of its roofline: the least time the chip
needs for the fold's algorithmic work, over ``table_fold_s``.

The work is counted as ``fold_roofline`` counts it, from the round's
accepted packets and shapes, never from the kernel's grid: each
accepted row read once at wire width, with its index and weight (and
q8 scale) once, and the ``(S*K, W)`` f32 client table and its
``(S*K,)`` presence read and written once per round.  K is the
configuration's clients a round."""
import importlib.util
from pathlib import Path


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fold_roofline = _sibling("fold_roofline")
table_fold_s = _sibling("table_fold_s")


def least_seconds(rnd: dict, n_clients: int, peaks: dict) -> float:
    """The fold's least time with the table's S*K rows for its slots."""
    return fold_roofline.least_seconds(
        dict(rnd, n_slots=rnd["n_slots"] * n_clients), peaks)


def read(obs):
    measured = table_fold_s.read(obs)
    if not measured:
        return None
    k = obs.config["n_clients"]
    least = sum(least_seconds(r, k, obs.peaks) for r in obs.rounds)
    return 100.0 * least / obs.n_rounds / measured
