"""The robust finalize's share of its roofline: the least time the
chip needs to move the finalize's bytes, over ``robust_finalize_s``.

Counted from shapes: the ``(S, K, W)`` f32 client table and its
``(S, K)`` presence read once, the ``(S, W)`` f32 aggregate and the
``(S,)`` contributor counts written once.  The rank compares run on the
vector unit and no matrix unit work is done, so bytes bound it."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_robust_finalize_s",
    Path(__file__).with_name("robust_finalize_s.py"))
robust_finalize_s = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(robust_finalize_s)


def finalize_bytes(n_slots: int, n_clients: int, payload: int) -> int:
    table = n_slots * n_clients * (payload + 1) * 4
    out = n_slots * (payload + 1) * 4
    return table + out


def read(obs):
    measured = robust_finalize_s.read(obs)
    if not measured:
        return None
    k = obs.config["n_clients"]
    least = sum(finalize_bytes(r["n_slots"], k, r["payload"])
                for r in obs.rounds) / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least / obs.n_rounds / measured
