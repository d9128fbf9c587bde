"""Plain replays of the eager engine's ring demux, for checking the
payload rows the compiled round ships against the dense ``(n_rows, B,
W)`` tensor its fold reads."""
import jax
import numpy as np

from repro.core import engine_compiled as ec
from repro.core.protocol import Kind


def first_copies(events):
    """The first copy of each (client, slot) DATA packet, as ``(Packet,
    payload)`` in arrival order: the accepted arrivals of a
    ``make_uplink_stream`` round, whose STARTs precede every DATA and
    whose ENDs follow it."""
    seen = set()
    for pkt, pay in events:
        if pkt.kind is not Kind.DATA or (pkt.client, pkt.index) in seen:
            continue
        seen.add((pkt.client, pkt.index))
        yield pkt, pay


def accepted_rows(events, decode: bool = False):
    """(slots, rows) of ``first_copies(events)``.  ``decode`` turns q8
    rows into f32 as the mixed-wire fallback does."""
    slots, rows = [], []
    for pkt, pay in first_copies(events):
        slots.append(pkt.index)
        row = np.asarray(pay)
        if decode:
            row = (row.astype(np.float32) * np.float32(pkt.scale)
                   if pkt.wire_dtype != "f32" else row.astype(np.float32))
        rows.append(row)
    return np.asarray(slots, np.int64), np.stack(rows)


def reference_schedule(cfg, events):
    """``build_drain_schedule`` on the accepted arrivals of one
    homogeneous-wire ``make_uplink_stream`` round, at unit weights."""
    firsts = list(first_copies(events))
    slots, rows = accepted_rows(events)
    q8 = firsts[0][0].wire_dtype != "f32"
    return ec.build_drain_schedule(
        slots.astype(np.int32), np.ones(len(slots), np.float32), rows,
        n_workers=cfg.n_workers, ring_capacity=cfg.ring_capacity,
        ring_assign=cfg.ring_assign,
        scales=(np.asarray([p.scale for p, _ in firsts], np.float32)
                if q8 else None),
        clients=np.asarray([p.client for p, _ in firsts], np.int32))


SCHEDULE_ARRAYS = ("idx", "weights", "payloads", "src", "workers",
                   "scales", "clients")


def schedule_copy(sched) -> dict:
    """A schedule's counts and arrays, copied out of its buffers."""
    out = {f: (None if getattr(sched, f) is None
               else np.array(getattr(sched, f))) for f in SCHEDULE_ARRAYS}
    out.update(n_batches=sched.n_batches, n_packets=sched.n_packets)
    return out


def assert_schedules_equal(got, want):
    """Two schedules, or their ``schedule_copy``, are bitwise equal."""
    got, want = (s if isinstance(s, dict) else schedule_copy(s)
                 for s in (got, want))
    assert got.keys() == want.keys()
    for f, a in got.items():
        b = want[f]
        if isinstance(a, np.ndarray):
            assert (a.shape, a.dtype) == (b.shape, b.dtype), f
            assert a.tobytes() == b.tobytes(), f
        else:
            assert a == b, f


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
