"""Plain replays of the eager engine's ring demux, for checking the
payload rows the compiled round ships against the dense ``(n_rows, B,
W)`` tensor its fold reads."""
import jax
import numpy as np

from repro.core import engine_compiled as ec
from repro.core.protocol import Kind


def accepted_rows(events, decode: bool = False):
    """(slots, rows) of the first copy of each (client, slot) DATA
    packet, in arrival order: the accepted arrivals of a
    ``make_uplink_stream`` round, whose STARTs precede every DATA and
    whose ENDs follow it.  ``decode`` turns q8 rows into f32 as the
    mixed-wire fallback does."""
    seen, slots, rows = set(), [], []
    for pkt, pay in events:
        if pkt.kind is not Kind.DATA or (pkt.client, pkt.index) in seen:
            continue
        seen.add((pkt.client, pkt.index))
        slots.append(pkt.index)
        row = np.asarray(pay)
        if decode:
            row = (row.astype(np.float32) * np.float32(pkt.scale)
                   if pkt.wire_dtype != "f32" else row.astype(np.float32))
        rows.append(row)
    return np.asarray(slots, np.int64), np.stack(rows)


def dense_payloads(slots, rows, *, n_workers: int, ring_capacity: int,
                   ring_assign: str = "rr", block: int = 128,
                   pad_batches: int = 8) -> np.ndarray:
    """The ``(n_rows, B, W)`` payload tensor of the eager batching: an
    arrival joins ring ``i % n_workers`` (rr) or ``slot % n_workers``, a
    full ring drains, partial rings flush at END in worker order, and
    every other cell is zero."""
    rings = [[] for _ in range(n_workers)]
    batches = []
    for i, s in enumerate(slots):
        wk = int(s) % n_workers if ring_assign == "slot" else i % n_workers
        rings[wk].append(i)
        if len(rings[wk]) == ring_capacity:
            batches.append(rings[wk])
            rings[wk] = []
    batches += [r for r in rings if r]
    n_rows = max(1, -(-len(batches) // pad_batches) * pad_batches)
    B = -(-ring_capacity // block) * block
    out = np.zeros((n_rows, B) + rows.shape[1:], rows.dtype)
    for r, members in enumerate(batches):
        out[r, :len(members)] = rows[members]
    return out


def expand(payloads: np.ndarray, src: np.ndarray) -> np.ndarray:
    """The ``(n_rows, B, W)`` tensor a fold reads from shipped rows:
    row ``src[r, c]`` at cell ``(r, c)``, zeros where ``src`` points
    past the end."""
    out = np.zeros(src.shape + payloads.shape[1:], payloads.dtype)
    live = src < len(payloads)
    out[live] = payloads[src[live]]
    return out


def assert_expands_to(sched, want: np.ndarray, ring_capacity: int):
    """The schedule ships ``n_rows * ring_capacity`` rows, and both
    expansions of them, on the host and on the device, are bitwise
    ``want``."""
    n_rows = sched.idx.shape[0]
    assert sched.payloads.shape == (n_rows * ring_capacity,
                                    want.shape[-1])
    assert sched.payloads.dtype == want.dtype
    assert sched.src.shape == sched.idx.shape
    assert (sched.src[sched.idx < 0] == len(sched.payloads)).all()
    host = expand(sched.payloads, sched.src)
    device = np.asarray(jax.jit(ec._take_rows)(sched.payloads, sched.src))
    for got in (host, device):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()
