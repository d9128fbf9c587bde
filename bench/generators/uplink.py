"""One FL round's uplink as the server's NIC sees it, built from a seed.

A vectorized copy of the semantics of ``core/server.py``'s
``make_uplink_stream`` and ``core/rounds.py``'s
``make_partial_round_events``, kept here so that no later change to
the program can change the traffic it is measured on:

- every client sends START, then one DATA packet per slot of its
  update, then END;
- ``burst_windows`` disjoint windows of ``burst_len`` consecutive slots
  are lost for every client at once, so those slots reach the server
  from nobody and the fallback to the previous global runs; every
  other packet is lost with probability ``loss_rate``, independently,
  as ``make_uplink_stream`` draws it;
- each surviving packet arrives twice with probability ``dup_rate``,
  the copy riding next to its original;
- one permutation reorders all DATA across clients and slots (UDP
  reordering at the server's RX);
- ``round(straggle_rate * K)`` clients straggle: a straggler delivers a
  prefix of its own arrival order (duplicates of delivered packets
  ride along until the stall) and never sends END.  The stall points
  are fixed quantiles of each straggler's packets, dealt to the
  stragglers by the seed, so every seed has the same straggler sizes
  in another order (uniform stall points would give every seed a
  different drain-schedule shape, and a compile of its own).

``truth`` is what a correct server accepts: the (client, slot) pairs
that reached it at least once, and the counts of duplicates and
stragglers.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Round:
    events: list            # [(Packet, payload row view)], arrival order
    payloads: np.ndarray    # (K, N, W) f32, or int8 on the q8 wire
    scales: np.ndarray | None   # (K, N) f32 per-packet q8 scales
    accepted: np.ndarray    # (K, N) bool: reached the server at least once
    duplicates: int         # DATA copies a correct server drops
    stragglers: int         # clients that never send END


def payload_tensor(rng: np.random.Generator, k: int, n: int, w: int,
                   wire: str, std: float):
    """The clients' packetized updates: normal f32 values, or uniform
    int8 in [-127, 127] with a per-packet scale 10**U(-4, -2)."""
    if wire == "f32":
        return (rng.standard_normal((k, n, w), dtype=np.float32)
                * np.float32(std)), None
    size = k * n * w
    raw = rng.bit_generator.random_raw(-(-size // 8))   # 8 bytes a draw
    q = raw.view(np.int8)[:size].reshape(k, n, w)
    np.maximum(q, -127, out=q)            # symmetric int8 range
    scales = (10.0 ** rng.uniform(-4.0, -2.0, (k, n))).astype(np.float32)
    return q, scales


def make_round(rng: np.random.Generator, payloads: np.ndarray,
               scales: np.ndarray | None, mix: dict) -> Round:
    """One round's events from the clients' payloads and a traffic mix
    (the parameters of ``traffic/<name>.json``)."""
    from repro.core.protocol import Kind, Packet

    k, n, _ = payloads.shape
    # losses: the burst windows (disjoint, for every client), and an
    # independent draw for every other packet
    length = int(mix.get("burst_len", 0))
    n_burst = int(mix.get("burst_windows", 0))
    keep = rng.random((k, n)) >= mix["loss_rate"]
    if n_burst:
        for j in rng.choice(n // length, n_burst, replace=False):
            keep[:, j * length:(j + 1) * length] = False
    cs, ns = np.nonzero(keep)
    reps = 1 + (rng.random(cs.size) < mix["dup_rate"])
    cl, sl = np.repeat(cs, reps), np.repeat(ns, reps)
    perm = rng.permutation(cl.size)
    cl, sl = cl[perm], sl[perm]

    n_strag = int(round(mix.get("straggle_rate", 0.0) * k))
    strag = np.zeros(k, bool)
    strag[rng.choice(k, n_strag, replace=False)] = True
    stall = np.full(k, np.iinfo(np.int64).max)
    if n_strag:
        quantiles = (np.arange(n_strag) + 0.5) / n_strag
        uniques = keep.sum(axis=1)[strag]
        stall[strag] = np.floor(rng.permutation(quantiles)
                                * uniques).astype(np.int64)
    # per event: how many distinct packets its client delivered before it
    first = np.zeros(cl.size, bool)
    first[np.unique(cl * n + sl, return_index=True)[1]] = True
    order = np.argsort(cl, kind="stable")       # arrival order per client
    f = first[order].astype(np.int64)
    seen = np.cumsum(f) - f
    group = np.searchsorted(cl[order], cl[order], side="left")
    before = np.empty(cl.size, np.int64)
    before[order] = seen - seen[group]
    sent = before < stall[cl]
    cl, sl = cl[sent], sl[sent]

    accepted = np.zeros((k, n), bool)
    accepted[cl, sl] = True
    events = [(Packet(Kind.START, c), None) for c in range(k)]
    if scales is None:
        events += [(Packet(Kind.DATA, c, s), payloads[c, s])
                   for c, s in zip(cl.tolist(), sl.tolist())]
    else:
        events += [(Packet(Kind.DATA, c, s, wire_dtype="q8",
                           scale=float(scales[c, s])), payloads[c, s])
                   for c, s in zip(cl.tolist(), sl.tolist())]
    events += [(Packet(Kind.END, c), None)
               for c in range(k) if not strag[c]]
    return Round(events, payloads, scales, accepted,
                 int(cl.size - accepted.sum()), n_strag)
