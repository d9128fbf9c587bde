"""Compiled device-resident round engine (DESIGN.md §3).

The eager ``ServerEngine`` (core/server.py) reproduces the paper's
RX → N-worker → TX pipeline faithfully but pays one Python-dispatched
device call per drained ring — so at benchmark scale it measures
dispatch, not the scatter kernel.  This module keeps the *semantics* of
that pipeline and compiles the *execution*:

1. **Demux pass** (host, vectorized numpy): the event stream — or the
   arrivals an engine recorded — is turned into a dense *drain
   schedule*: ``(n_batches, B)`` slot/weight arrays, one row per
   drained ring batch, padded with inert ``idx = -1`` / ``weight = 0``
   entries, and a ``src`` column naming the payload row each entry
   folds.  The payload rows themselves ship once, in arrival order
   (``n_batches * ring_capacity`` rows with a zero tail), and every
   round, on any topology and in async mode too, expands them to the
   ``(n_batches, B, W)`` fold input on the device (``_take_rows``).  The
   schedule reproduces the eager engine's batching exactly: round-robin
   or slot demux onto ``n_workers`` rings, a drain whenever a ring
   reaches capacity (in arrival order), and the END flush of partial
   rings in worker order.  Because approx mode's last-writer-wins race
   is scoped to a drain batch, identical batching makes the compiled
   engine bitwise identical to the eager one in *both* modes.

2. **One ``lax.scan`` per round** (device): the whole schedule runs
   through ``packet_scatter_accum_scan`` inside a single jitted call;
   the ``(total, counts)`` accumulators are donated
   (``donate_argnums``) and carried through the scan in place — no
   per-drain reallocation.  The END count-normalized divide, the
   per-slot fallback to the previous global, and (optionally) the TX
   downlink fallback + APFL blend are fused into the same call, so a
   full server round is exactly one device dispatch.

3. **Round overlap** (``run_compiled_rounds``): a double-buffered
   driver dispatches round r and, while the device executes it (JAX
   async dispatch), demuxes round r+1 on the host — the executable
   analogue of the paper's dedicated RX core running ahead of the
   workers (§3.2).

4. **Sharding** (``EngineConfig(shards=N)``, DESIGN.md §7): the drain
   batches are split per shard by ring ownership (``leaf_layout``),
   each shard ships only its own payload rows and ``src`` column, and
   folds its batches into shard-local ``(total, counts)`` partials —
   the DPU's per-core partial sums — combined by one ``psum`` over the
   ``'worker'`` device mesh (``runtime.sharding.worker_mesh``) before
   the fused END divide; a vmap emulation covers platforms with fewer
   devices, bitwise identically.

5. **Hierarchy** (``EngineConfig(hosts=H)``, DESIGN.md §12): the
   accepted arrivals are partitioned by contiguous client-range
   ownership (``partition_schedule_by_host``), each leaf host
   re-demuxes only its own clients' packets with its own rings, the
   shard split applies within each host, and the fold combines with
   one psum per level of the 2-D ``('host', 'worker')`` mesh.  One
   host function (``leaf_layout``) and one device function
   (``kernels.packet_scatter.fold_leaves``) carry the topology for the
   synchronous and the async rounds alike.

Invariants the tests pin (tests/test_engine_compiled.py,
test_engine_sharded.py, test_engine_hier.py):

- *Bitwise parity*: on integer-valued payloads in exact mode, every
  ``(hosts, shards)`` factorization — including the nested-vmap
  emulation — produces bit-identical ``(total, counts, new_global)``
  to the unsharded compiled round, which is itself bit-identical to
  the eager ``ServerEngine``.  Approx mode is bitwise vs the engine
  with the *same* batching (eager per-host twin at ``hosts > 1``).
- *Conservation*: accepted = enqueued arrivals; per-host
  ``data_enqueued`` sums to the global count; dedup/phase/malformed
  drops are disjoint buckets.

Entry points: ``run_compiled_round`` mirrors
``server.run_engine_round`` (which routes here when
``EngineConfig.compile`` is set); ``ServerEngine`` with
``compile=True`` keeps the per-packet ``rx`` API and dispatches the
recorded round from ``finalize_round`` / ``finalize_and_distribute``.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import threading
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import expand_packet_mask
from repro.core.packets import depacketize
from repro.core.protocol import Kind
# QuorumError is re-exported so callers of the bulk path can catch it
# from either module
from repro.core.server import (AsyncResult, AsyncState, AsyncStats,
                               EngineConfig, EngineStats, QuorumError,
                               RoundResult, UpdateRecord,  # noqa: F401
                               check_quorum, payload_malformed)
from repro.kernels.packet_scatter import (BLOCK_PKTS, fold_leaves,
                                          norm_clip_weights,
                                          packet_scatter_accum_scan,
                                          packet_table_scatter,
                                          robust_finalize_jnp,
                                          robust_finalize_pallas,
                                          staleness_weights)
from repro.runtime.sharding import (HOST_AXIS, WORKER_AXIS,
                                    host_worker_mesh, worker_mesh)

# Profiler spans of the served round, as ``jax.profiler.TraceAnnotation``
# (inert when no trace runs).  Each opens once or twice a round, never
# per event: ``fl.round`` holds ``run_compiled_round``; ``fl.demux``
# (host RX) is tiled by its per-event scan, numpy gate, payload stacks
# and drain schedule; ``fl.h2d`` holds the host-to-device copies as the
# caller hands them over (PjRt lays them out on its own threads, mostly
# after the span closes) and ``fl.launch`` the accumulators' allocation
# and the jitted call.  ``run_compiled_rounds`` shares ``demux_events``
# and ``dispatch_round``: it opens every span but ``fl.round``, and
# allocates its accumulators outside ``fl.launch``.
SPANS = ("fl.round", "fl.demux", "fl.demux.scan", "fl.demux.gate",
         "fl.demux.stack", "fl.demux.schedule", "fl.h2d", "fl.launch")
_span = jax.profiler.TraceAnnotation
# the robust modes that fold into a per-slot client table (DESIGN.md
# §11) in place of the (S, W) sum and (S,) counts
TABLE_MODES = ("trimmed_mean", "median")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas(cfg: EngineConfig) -> bool:
    """Scan-body selection: the Pallas grid kernel is the production TPU
    body; everywhere else the bitwise jnp twin runs (an interpreted grid
    would unroll hundreds of HLO ops per scan step)."""
    if cfg.scan_body == "pallas":
        return True
    if cfg.scan_body == "jnp":
        return False
    return cfg.use_kernel and not _interpret()


# ---------------------------------------------------------------------------
# Host buffer pool: the demux's payload arrays, recycled across rounds
# ---------------------------------------------------------------------------

# idle buffers kept per (row width, dtype): an f32 round leases two (its
# candidate stack and its shipped rows) while the previous round's
# shipped rows may still be held by their transfer
POOL_IDLE = 4
# a new buffer is this fraction larger than the rows asked for, so a
# round with a few more candidates than the last still fits
_POOL_SLACK = 1 / 8


def _refcounts(bufs: list) -> List[int]:
    return [sys.getrefcount(b) for b in bufs]


# the count of an array that only the pool's list holds, taken the way
# ``_HostPool`` takes it
_FREE_REFS = _refcounts([np.empty(0)])[0]


class _HostPool:
    """The payload buffers ``demux_events`` writes, kept across rounds:
    the analogue of the paper's DPDK mempool, whose mbufs are allocated
    once and recycled by refcount.  Large arrays made anew each round
    are mapped, faulted in, zeroed and unmapped each round; a pooled
    buffer's pages stay mapped.

    ``lease`` returns a view of the first ``rows`` rows of a pooled
    array of that width and dtype with at least that many rows (the
    smallest that fits), allocating and keeping a new one only when
    none is free.  A buffer is free when nothing outside the pool
    references it: every holder of a lease (a ``DrainSchedule``, its
    ``arrivals`` view, ``leaf_layout``'s views, JAX while a transfer
    still reads it) keeps the owning array alive, and so its reference
    count above the pool's own.  After each lease at most ``POOL_IDLE``
    free buffers stay per width and dtype, the least recently leased
    dropped first, so a server whose shapes drift does not grow.
    """

    def __init__(self):
        self._bufs: List[np.ndarray] = []   # least recently leased first
        self._lock = threading.Lock()
        self.leases = 0
        self.reused = 0

    def lease(self, rows: int, width: int, dtype) -> np.ndarray:
        key = (int(width), np.dtype(dtype))
        with self._lock:
            refs = _refcounts(self._bufs)
            idle = [b for b, n in zip(self._bufs, refs)
                    if n == _FREE_REFS and (b.shape[1], b.dtype) == key]
            fits = [b for b in idle if len(b) >= rows]
            self.leases += 1
            self.reused += bool(fits)
            buf = (min(fits, key=len) if fits else
                   np.empty((rows + int(rows * _POOL_SLACK), key[0]), key[1]))
            spare = [b for b in idle if b is not buf]
            drop = {id(b) for b in spare[:max(0, len(spare) - POOL_IDLE)]}
            self._bufs = [b for b in self._bufs
                          if b is not buf and id(b) not in drop] + [buf]
            return buf[:rows]


_POOL = _HostPool()


def host_pool_stats() -> dict:
    """The demux's buffer pool: leases made so far, how many of them
    reused a pooled buffer (the rest allocated one), and the buffers it
    keeps, leased or idle."""
    return dict(leases=_POOL.leases, reused=_POOL.reused,
                kept=len(_POOL._bufs))


# ---------------------------------------------------------------------------
# Demux: arrivals -> dense drain schedule
# ---------------------------------------------------------------------------

# drain-schedule row counts are bucketed to a multiple of this, so lossy
# round-to-round batch-count jitter reuses one jit trace
PAD_BATCHES = 8


@dataclasses.dataclass
class DrainSchedule:
    """Per-round drain schedule (host arrays, ready to dispatch).

    One row per drain batch; rows beyond ``n_batches`` (row counts are
    bucketed to a multiple of ``PAD_BATCHES``) and unused columns are
    inert: ``idx = -1`` matches no slot, weight 0 is inert in sums and
    counts.

    ``payloads`` holds only the rows a round ships: the accepted
    arrivals in arrival order, then a zero tail up to ``n_rows *
    ring_capacity`` rows, a length fixed by the bucketed row count so
    the shape does not move with the accepted count.  ``src`` says which
    payload row each schedule cell folds; inert cells point one past the
    end, where the device's fill-mode gather (``_take_rows``) reads
    zeros.  ``leaf_layout`` splits a schedule into the same form per
    shard or (host, shard) leaf.
    """
    idx: np.ndarray         # (n_rows, B) int32 slot rows
    weights: np.ndarray     # (n_rows, B) f32 per-arrival FedAvg weights
    payloads: np.ndarray    # (n_rows * ring_capacity, W) payload rows: f32
                            # wire, or int8 when ``scales`` is present
    src: np.ndarray         # (n_rows, B) int32 payload row per cell;
                            # inert cells hold len(payloads)
    n_batches: int          # real drain batches (rest is padding)
    n_packets: int          # accepted arrivals scheduled
    workers: np.ndarray     # (n_rows,) owning worker ring per batch (-1
                            # for padding); the shard split keys on it
    arrivals: tuple         # the accepted arrival-order columns this
                            # schedule was built from: (slots, weights,
                            # payloads, scales, staleness, clients) —
                            # cheap references (payloads is a view of the
                            # shipped rows), kept so the hierarchical
                            # path can re-demux per host (DESIGN.md §12)
    scales: Optional[np.ndarray] = None    # (n_rows, B) f32 per-packet
                                           # q8 dequant scales (0 inert);
                                           # None on the f32 wire path
    staleness: Optional[np.ndarray] = None # (n_rows, B) f32 per-packet
                                           # update age at fold time
                                           # (DESIGN.md §10); None on
                                           # synchronous rounds
    clients: Optional[np.ndarray] = None   # (n_rows, B) int32 sender per
                                           # packet (-1 inert) — the
                                           # robust table modes' combined
                                           # index needs it (DESIGN.md
                                           # §11); None when untracked


def build_drain_schedule(slots: np.ndarray, weights: np.ndarray,
                         payloads: np.ndarray, *, n_workers: int,
                         ring_capacity: int, ring_assign: str = "rr",
                         block_pkts: int = BLOCK_PKTS,
                         pad_batches: int = PAD_BATCHES,
                         scales: Optional[np.ndarray] = None,
                         staleness: Optional[np.ndarray] = None,
                         clients: Optional[np.ndarray] = None
                         ) -> DrainSchedule:
    """Vectorized replay of the eager engine's ring demux.

    slots (n,) int32 / weights (n,) f32 / payloads (n, W) f32 are the
    *accepted* (post-FSM, post-dedup) arrivals in arrival order.  The
    batching reproduces ``ServerEngine`` exactly: arrival i goes to
    worker ``i % n_workers`` (rr) or ``slot % n_workers`` (slot demux);
    a ring drains — in arrival order of its capacity-th packet — when
    full, and partial rings flush at END in worker order.  Batch rows
    are padded to ``B = ceil(capacity / block_pkts) * block_pkts``, the
    same inert padding the eager ``scatter_add`` applies per drain.

    ``scales`` (n,) f32 marks a q8 round: payloads are then the int8
    wire rows and the schedule carries the per-packet scale column next
    to the weights (DESIGN.md §9); padding entries get scale 0, which
    dequantizes padding to 0 exactly like the f32 inert rows.

    ``staleness`` (n,) f32 is the async mode's per-packet update age at
    fold time (DESIGN.md §10), carried as one more column; padding gets
    staleness 0, inert because its weight is 0 in every weighting mode.
    """
    sched = _drain_layout(slots, weights, int(payloads.shape[1]),
                          n_workers=n_workers, ring_capacity=ring_capacity,
                          ring_assign=ring_assign, block_pkts=block_pkts,
                          pad_batches=pad_batches, scales=scales,
                          staleness=staleness, clients=clients)
    sched.payloads[:sched.n_packets] = payloads
    return sched


def _drain_layout(slots: np.ndarray, weights: np.ndarray, width: int, *,
                  n_workers: int, ring_capacity: int, ring_assign: str = "rr",
                  block_pkts: int = BLOCK_PKTS,
                  pad_batches: int = PAD_BATCHES,
                  scales: Optional[np.ndarray] = None,
                  staleness: Optional[np.ndarray] = None,
                  clients: Optional[np.ndarray] = None) -> DrainSchedule:
    """``build_drain_schedule`` less its payload copy: ``payloads``
    comes back with its zero tail and its first ``n`` rows unset, for
    the caller to fill with the accepted rows in arrival order, so each
    row is copied once.  It is leased from the host buffer pool
    (``_HostPool``), so its pages are not new each round."""
    n = int(slots.shape[0])
    B = ring_capacity + (-ring_capacity) % block_pkts
    pk_dtype = np.float32 if scales is None else np.int8
    if ring_assign == "slot":
        worker = slots.astype(np.int64) % n_workers
    else:
        worker = np.arange(n, dtype=np.int64) % n_workers
    pos = np.zeros(n, np.int64)
    for wk in range(n_workers):           # n_workers is tiny (paper: 5)
        m = worker == wk
        pos[m] = np.arange(int(m.sum()))
    b_in_w = pos // ring_capacity
    col = pos % ring_capacity
    key = worker * (n + 1) + b_in_w       # unique per (worker, batch)
    uniq, inv, sizes = np.unique(key, return_inverse=True,
                                 return_counts=True)
    last = np.zeros(len(uniq), np.int64)
    np.maximum.at(last, inv, np.arange(n, dtype=np.int64))
    full = sizes == ring_capacity
    # full batches drained at the arrival of their capacity-th packet
    # (chronological); partial rings flush after every arrival, in
    # worker order — uniq is sorted by (worker, batch) already, and a
    # worker has at most one partial ring
    order_key = np.where(full, last, n + uniq)
    order = np.argsort(order_key, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    row = rank[inv]
    nb = len(uniq)
    # an empty round still has one (inert) row
    n_rows = max(1, (nb + (-nb) % pad_batches) if pad_batches > 1 else nb)
    idx = np.full((n_rows, B), -1, np.int32)
    w = np.zeros((n_rows, B), np.float32)
    idx[row, col] = slots
    w[row, col] = weights
    # a batch holds at most ring_capacity arrivals, so n <= rows_out; the
    # leading size follows n_rows alone, never the accepted count
    rows_out = n_rows * ring_capacity
    src = np.full((n_rows, B), rows_out, np.int32)
    src[row, col] = np.arange(n, dtype=np.int32)
    pk = _POOL.lease(rows_out, width, pk_dtype)
    pk[n:] = 0
    sc = None
    if scales is not None:
        sc = np.zeros((n_rows, B), np.float32)
        sc[row, col] = scales
    st = None
    if staleness is not None:
        st = np.zeros((n_rows, B), np.float32)
        st[row, col] = staleness
    cl = None
    if clients is not None:
        cl = np.full((n_rows, B), -1, np.int32)
        cl[row, col] = clients
    row_worker = np.full(n_rows, -1, np.int64)
    row_worker[rank] = uniq // (n + 1)            # batch key -> its worker
    return DrainSchedule(idx, w, pk, src, int(nb), n, row_worker,
                         (slots, weights, pk[:n], scales, staleness,
                          clients), scales=sc, staleness=st, clients=cl)


def partition_schedule_by_host(sched: DrainSchedule, n_hosts: int,
                               n_clients: int, *, n_workers: int,
                               ring_capacity: int, ring_assign: str = "rr"
                               ) -> List[DrainSchedule]:
    """Demux a round's arrivals per leaf host (DESIGN.md §12).

    Host ``h`` owns the contiguous client range
    ``runtime.sharding.client_range(h, n_hosts, n_clients)`` — every
    accepted arrival belongs to exactly one host and the per-host
    subsequences concatenate (in client-range order) to a permutation
    of the full arrival stream: the schedule-partition property
    (tests/test_engine_hier.py).  Each host then replays the *eager
    per-host engine's* ring demux over only its own arrivals, in their
    original relative order, with its own rings and rr pointer — a real
    leaf host never sees other hosts' packets, so its batch composition
    must be computed from its filtered stream, not sliced out of the
    global schedule (under rr demux the two differ).  That is why
    ``DrainSchedule`` keeps its ``arrivals`` columns.

    Runs *before* any robust-table index rewrite (the rewrite keys on
    the original slot/client columns) and before the shard split
    (ring→shard ownership applies within each host): ``leaf_layout``
    keeps that order.
    """
    slots, weights, payloads, scales, staleness, clients = sched.arrivals
    assert clients is not None, \
        "hierarchical demux needs a client-tracked schedule"
    from repro.runtime.sharding import client_owner
    owner = client_owner(clients, n_hosts, n_clients)
    out = []
    for h in range(n_hosts):
        m = owner == h
        out.append(build_drain_schedule(
            np.asarray(slots)[m], np.asarray(weights)[m],
            np.asarray(payloads)[m], n_workers=n_workers,
            ring_capacity=ring_capacity, ring_assign=ring_assign,
            scales=None if scales is None else np.asarray(scales)[m],
            staleness=(None if staleness is None
                       else np.asarray(staleness)[m]),
            clients=np.asarray(clients)[m]))
    return out


class Leaves(NamedTuple):
    """One dispatch's fold operands, laid out per leaf (DESIGN.md §7,
    §12).

    A leaf is the single chip, a worker shard, or a (host, shard) pair.
    Every array leads with a schedule axis (one per round, one per emit
    window for an async call), then the leaf axes ``()``, ``(shards,)``
    or ``(hosts, shards)``.  Each leaf ships what a single-chip round
    ships: its own accepted payload rows and then a zero tail,
    ``R * ring_capacity`` rows for the common row count ``R``, and a
    leaf-local ``src`` column whose inert cells point one past the end.
    """
    idx: np.ndarray                   # (..., R, B) int32 slot rows
    weights: np.ndarray               # (..., R, B) f32
    payloads: np.ndarray              # (..., R * ring_capacity, W)
    src: np.ndarray                   # (..., R, B) int32
    scales: Optional[np.ndarray]      # (..., R, B) f32, q8 wire only
    staleness: Optional[np.ndarray]   # (..., R, B) f32, async only


def _topology(cfg: EngineConfig):
    """``(axes, sizes, mesh)`` of a config's leaves, outermost axis
    first; ``mesh`` is None on one chip and where the CPU emulates the
    leaves on one device (``runtime.sharding``)."""
    if cfg.hosts > 1:
        return ((HOST_AXIS, WORKER_AXIS), (cfg.hosts, cfg.shards),
                host_worker_mesh(cfg.hosts, cfg.shards))
    if cfg.shards > 1:
        return (WORKER_AXIS,), (cfg.shards,), worker_mesh(cfg.shards)
    return (), (), None


def leaf_layout(cfg: EngineConfig, scheds: Sequence[DrainSchedule]
                ) -> Leaves:
    """Lay one dispatch's drain schedules out per leaf: the round's
    schedule, or an async call's per-window schedules.

    The order matters.  ``partition_schedule_by_host`` first re-demuxes
    each host's own arrivals with its own rings; then the robust table
    modes rewrite the index (it keys on the original slot and client
    columns); then shard ``s`` takes the drain batches of the worker
    rings ``w`` with ``w % shards == s`` — the paper's static ring→core
    pinning, which keeps every drain batch (approx mode's last-writer-
    wins window) whole on one shard.  Batch composition is unchanged;
    only the fold of batches into accumulators is regrouped, which keeps
    any factorization bitwise the single chip on integer-valued
    payloads.  Leaves are padded with inert rows to a common row count
    bucketed to ``PAD_BATCHES``; a leaf that owns no ring is inert.  A
    lone schedule on one chip comes back as it is, without a copy.
    """
    _, sizes, _ = _topology(cfg)
    per_sched = []
    for sched in scheds:
        hosts = [sched]
        if cfg.hosts > 1:
            hosts = partition_schedule_by_host(
                sched, cfg.hosts, cfg.n_clients, n_workers=cfg.n_workers,
                ring_capacity=cfg.ring_capacity,
                ring_assign=cfg.ring_assign)
        if cfg.agg_mode in TABLE_MODES:
            hosts = [_combined_table_sched(h, cfg.n_clients) for h in hosts]
        per_sched.append(hosts)
    if not sizes and len(scheds) == 1:
        h = per_sched[0][0]
        return Leaves(*(None if a is None else a[None] for a in (
            h.idx, h.weights, h.payloads, h.src, h.scales, h.staleness)))
    leaves = [(h, np.nonzero(h.workers[:h.n_batches] % cfg.shards == k)[0])
              for hosts in per_sched for h in hosts
              for k in range(cfg.shards)]
    R = max(1, max(len(rows) for _, rows in leaves))
    R += (-R) % PAD_BATCHES
    end = R * cfg.ring_capacity
    first = leaves[0][0]
    n, B = len(leaves), first.idx.shape[1]
    idx = np.full((n, R, B), -1, np.int32)
    w = np.zeros((n, R, B), np.float32)
    pk = np.zeros((n, end, first.payloads.shape[1]), first.payloads.dtype)
    src = np.full((n, R, B), end, np.int32)
    sc = None if first.scales is None else np.zeros((n, R, B), np.float32)
    st = (None if first.staleness is None
          else np.zeros((n, R, B), np.float32))
    for i, (h, rows) in enumerate(leaves):
        r = len(rows)
        for out, col in ((idx, h.idx), (w, h.weights), (sc, h.scales),
                         (st, h.staleness)):
            if out is not None:
                out[i, :r] = col[rows]
        # the leaf's accepted rows, renumbered from 0, then its zero tail
        old = h.src[rows]
        live = old < len(h.payloads)
        k = int(live.sum())
        pk[i, :k] = h.payloads[old[live]]
        src[i, :r][live] = np.arange(k, dtype=np.int32)
    lead = (len(scheds),) + sizes
    return Leaves(*(None if a is None else a.reshape(lead + a.shape[1:])
                    for a in (idx, w, pk, src, sc, st)))


def approx_lost_updates(sched: DrainSchedule, n_shards: int = 1
                        ) -> np.ndarray:
    """Per-shard count of approx-mode lost updates (race accounting).

    Within one drained batch every same-slot arrival beyond the last
    writer is lost in approx mode, so the loss of a batch is (weighted
    arrivals) − (distinct slots hit).  Batches are split to shards by
    ring ownership exactly as ``leaf_layout`` does, hence the
    per-shard race window: each shard loses only what its own rings
    race, summing to the unsharded total — sharding splits the lost
    updates ≈ 1/n_shards per shard without changing the global race
    (EXPERIMENTS.md §Shard-scaling).
    """
    lost = np.zeros(n_shards, np.int64)
    live = sched.workers[:sched.n_batches]
    for r in range(sched.n_batches):
        valid = (sched.idx[r] >= 0) & (sched.weights[r] > 0)
        hits = int(valid.sum())
        distinct = len(np.unique(sched.idx[r][valid]))
        lost[int(live[r]) % n_shards] += hits - distinct
    return lost


@functools.partial(jax.profiler.annotate_function, name="fl.demux")
def demux_events(cfg: EngineConfig, events: Iterable,
                 weights: Optional[np.ndarray] = None
                 ) -> Tuple[DrainSchedule, EngineStats, np.ndarray]:
    """Bulk RX: one pass over ``(Packet, payload)`` events, vectorized
    FSM gating + dedup, -> (schedule, stats, up_mask (K, N) numpy).

    Replicates ``ServerEngine.rx`` acceptance for client→server
    uplink streams: DATA is accepted iff it lands strictly between the
    client's first START and the first END after it, and only the first
    copy of each (client, slot) counts.  Control replies are *counted*
    (stats parity with the FSM) but not materialized — callers that
    need the reply packets use the per-packet API.

    ``cfg.round_deadline`` closes the uplink barrier at that event
    position, exactly as the eager engine's rx does (DESIGN.md §8):
    only pre-deadline STARTs/ENDs frame a client, DATA at or past the
    deadline is ``late_dropped``, clients without an accepted END are
    ``stragglers_timed_out`` (their accepted arrivals stay in the
    schedule — a deadline-closed round is bitwise the same round with
    the stragglers' undelivered packets as wire losses), late ENDs from
    timed-out clients are still grace-ack-counted, and the
    ``min_clients`` quorum guard raises before any device work.

    The call runs in the ``fl.demux`` span, tiled by its phases:
    ``fl.demux.scan`` (the per-event pass), ``fl.demux.gate`` (the numpy
    FSM gate, deadline cut, malformed check and dedup),
    ``fl.demux.stack`` (the payload stacks, written into buffers leased
    from the host pool; the round's last such span carries the pool's
    running ``pool_leases`` and ``pool_reused`` counts) and
    ``fl.demux.schedule``.
    """
    K, n_slots = cfg.n_clients, cfg.n_slots
    wts = (np.ones(K, np.float32) if weights is None
           else np.asarray(weights, np.float32))
    with _span("fl.demux.scan"):
        d_c: List[int] = []
        d_s: List[int] = []
        d_pay: List = []
        d_pos: List[int] = []
        d_q8: List[bool] = []
        d_sc: List[float] = []
        s_c: List[int] = []
        s_pos: List[int] = []
        e_c: List[int] = []
        e_pos: List[int] = []
        # local bindings keep the one unavoidable per-event pass cheap
        data_k, start_k, end_k = Kind.DATA, Kind.START, Kind.END
        dc_ap, ds_ap = d_c.append, d_s.append
        dpay_ap, dpos_ap = d_pay.append, d_pos.append
        dq_ap, dsc_ap = d_q8.append, d_sc.append
        pos = 0
        for packet, payload in events:
            kind = packet.kind
            if kind is data_k:
                dc_ap(packet.client)
                ds_ap(packet.index)
                dpay_ap(payload)
                dpos_ap(pos)
                dq_ap(packet.wire_dtype != "f32")
                dsc_ap(packet.scale)
            elif kind is start_k:
                s_c.append(packet.client)
                s_pos.append(pos)
            elif kind is end_k:
                e_c.append(packet.client)
                e_pos.append(pos)
            pos += 1
    with _span("fl.demux.gate"):
        inf = pos + 1
        # the uplink barrier closes at the deadline: events at pos >= cut
        # are past the close (cut = inf replays the no-deadline behavior)
        deadline_set = cfg.round_deadline is not None
        cut = cfg.round_deadline if deadline_set else inf
        first_start = np.full(K, inf, np.int64)
        if s_c:
            sc, sp = np.asarray(s_c), np.asarray(s_pos, np.int64)
            pre = sp < cut
            np.minimum.at(first_start, sc[pre], sp[pre])
        first_end = np.full(K, inf, np.int64)
        if e_c:
            ec, ep = np.asarray(e_c), np.asarray(e_pos, np.int64)
            after = (ep > first_start[ec]) & (ep < cut)
            np.minimum.at(first_end, ec[after], ep[after])
        stats = EngineStats()
        # clients short of their END at the close are this round's
        # stragglers
        timed = (first_end >= inf) if deadline_set else np.zeros(K, bool)
        stats.stragglers_timed_out = int(np.sum(timed))
        check_quorum(int(np.sum(first_end < inf)), cfg.min_clients,
                     stats.stragglers_timed_out)
        if s_c:       # STARTs in any post-START phase are (re-)acked; a
                      # TIMED_OUT client's round is closed — no ack past cut
            stats.control_replies += int(np.sum(
                (sp >= first_start[sc]) & ~(timed[sc] & (sp >= cut))))
        if e_c:       # ENDs at/after the accepted END are (re-)acked, and a
                      # timed-out straggler's late END is grace-acked too
            stats.control_replies += int(np.sum(
                (ep >= first_end[ec]) | (timed[ec] & (ep >= cut))))
        up = np.zeros((K, n_slots), np.float32)
        # a round with no DATA goes the same way, to an empty schedule
        dc = np.asarray(d_c, np.int64)
        ds = np.asarray(d_s, np.int64)
        dp = np.asarray(d_pos, np.int64)
        # every DATA packet past the deadline is late (the eager rx drops
        # it before the FSM gate); pre-deadline DATA outside its client's
        # START..END frame is phase-dropped as before
        pre = dp < cut
        stats.late_dropped = int(np.sum(~pre))
        # wire hardening (DESIGN.md §11): non-finite f32 payloads and
        # zero/negative/non-finite q8 scales are dropped between the
        # deadline gate and the FSM gate, before the dedup set — same
        # bucket order as the eager rx, so a clean retransmission of a
        # poisoned slot is still accepted.  Vectorized: one payload
        # stack per round, not one isfinite call per packet
        nd = len(d_c)
        bad = np.zeros(nd, bool)
        q8_arr = np.asarray(d_q8, bool)
        sc_arr = np.asarray(d_sc, np.float32)
        if q8_arr.any():
            qi = np.nonzero(q8_arr)[0]
            bad[qi] = ~(np.isfinite(sc_arr[qi]) & (sc_arr[qi] > 0))
        pos_in_f32 = np.full(nd, -1, np.int64)
        f32_stack = None
        fi = np.nonzero(~q8_arr & np.asarray(
            [p is not None for p in d_pay], bool))[0]
    if len(fi):
        with _span("fl.demux.stack"):
            # the candidate rows, copied once into a pooled buffer
            f32_stack = _POOL.lease(len(fi), np.shape(d_pay[fi[0]])[0],
                                    np.float32)
            f32_stack[...] = [d_pay[i] for i in fi]
    with _span("fl.demux.gate"):
        if len(fi):
            bad[fi] = ~np.isfinite(f32_stack).all(axis=1)
            pos_in_f32[fi] = np.arange(len(fi))
        stats.malformed_dropped = int(np.sum(pre & bad))
        gate = pre & ~bad
        frame_ok = (dp > first_start[dc]) & (dp < first_end[dc])
        phase_ok = gate & frame_ok
        stats.phase_dropped = int(np.sum(gate & ~frame_ok))
        ok_rows = np.nonzero(phase_ok)[0]
        keys = dc[ok_rows] * n_slots + ds[ok_rows]
        _, first_idx = np.unique(keys, return_index=True)
        acc_rows = ok_rows[np.sort(first_idx)]    # arrival order preserved
        stats.duplicates_dropped = int(len(ok_rows) - len(first_idx))
        stats.data_enqueued = int(len(acc_rows))
        up[dc[acc_rows], ds[acc_rows]] = 1.0
        n_q8 = int(q8_arr[acc_rows].sum())
        # a homogeneous q8 round stays int8 end to end and the per-packet
        # scale column rides beside the weights — the only f32 form of the
        # uplink is built inside the scan body
        q8_round = 0 < n_q8 == len(acc_rows)
    with _span("fl.demux.schedule"):
        sched = _drain_layout(
            ds[acc_rows].astype(np.int32), wts[dc[acc_rows]], cfg.payload,
            n_workers=cfg.n_workers, ring_capacity=cfg.ring_capacity,
            ring_assign=cfg.ring_assign,
            scales=sc_arr[acc_rows] if q8_round else None,
            clients=dc[acc_rows].astype(np.int32))
    with _span("fl.demux.stack", pool_leases=_POOL.leases,
               pool_reused=_POOL.reused):
        # copy only the *accepted* payload rows, once, straight into the
        # shipped array: dropped DATA may legally carry no payload (the
        # eager rx phase-drops before its assert)
        pay = sched.payloads[:len(acc_rows)]
        if q8_round:
            # numpy fills the view from the list directly, no temporary
            pay[...] = [d_pay[i] for i in acc_rows]
        elif n_q8:
            # mixed f32/q8 round: correctness fallback — decode the q8 rows
            # host-side into one f32 schedule (same elementwise q * scale
            # the fused kernel applies, so numerics are unchanged)
            pay[...] = [
                np.asarray(d_pay[i], np.int8).astype(np.float32)
                * np.float32(d_sc[i]) if d_q8[i]
                else np.asarray(d_pay[i], np.float32)
                for i in acc_rows]
        elif len(acc_rows):
            # the malformed pass already stacked every candidate f32 row —
            # gather from that stack instead of a second copy ("wrap", as
            # mode "raise" would buffer the gather before the copy)
            np.take(f32_stack, pos_in_f32[acc_rows], axis=0, out=pay,
                    mode="wrap")
    stats.batches_drained = sched.n_batches
    return sched, stats, up


# ---------------------------------------------------------------------------
# Device: one jitted lax.scan per round, donated accumulators
# ---------------------------------------------------------------------------

def _take_rows(rows, src):
    """The shipped payload rows laid out, on the device, as the
    ``(n_rows, B, W)`` fold input.  Inert cells hold ``len(rows)``, past
    the end, where ``mode="fill"`` reads zeros (an index of -1 would
    wrap onto the last row)."""
    return jnp.take(rows, src, axis=0, mode="fill", fill_value=0)


def _fold_leaf(ops: Leaves, acc, cnt, *, exact: bool, use_pallas: bool,
               block_slots: int, block_pkts: int, interpret: bool,
               weigh=None, table: bool = False):
    """One leaf's fold: gather its shipped rows (``_take_rows``), weigh
    each packet (``weigh(ops, rows) -> weights``, elementwise per packet,
    so no regrouping of batches can change it), and run the drain scan.
    The robust table on the jnp body folds as one flat scatter instead
    (``packet_table_scatter``): its combined indices are unique, and the
    scan's per-batch ``(S·K, B)`` one-hot routing is quadratic in the
    table height; pallas keeps the blocked grid (its production body)."""
    pk = _take_rows(ops.payloads, ops.src)
    w = ops.weights if weigh is None else weigh(ops, pk)
    if table and not use_pallas:
        return packet_table_scatter(ops.idx, w, pk, acc, cnt,
                                    sched_scales=ops.scales)
    return packet_scatter_accum_scan(
        ops.idx, w, pk, acc, cnt, sched_scales=ops.scales, exact=exact,
        use_pallas=use_pallas, block_slots=block_slots,
        block_pkts=block_pkts, interpret=interpret)


def _mean(total, counts):
    """The END divide: per-slot sum over its count, 0 where none came."""
    avg = total / jnp.maximum(counts, 1e-12)[:, None]
    return jnp.where(counts[:, None] > 0, avg, 0.0)


def _publish(agg, have, prev_global, client_flats, down_mask, *,
             payload: int, n_params: int, mix_alpha: float):
    """The END step every round shares -> (new_global, new_flats|None):
    the ``(S, W)`` aggregate depacketized, each slot without a
    contribution (``have`` False) falling back to ``prev_global``; then,
    when ``client_flats`` is given, the TX downlink with each client
    keeping its own model where ``down_mask`` lost the packet, and the
    APFL blend ``mix_alpha``."""
    agg_flat = depacketize(agg, n_params)
    have = expand_packet_mask(have, payload, n_params)
    new_global = jnp.where(have, agg_flat, prev_global)
    if client_flats is None:
        return new_global, None
    down_elem = expand_packet_mask(down_mask, payload, n_params)
    new_flats = jnp.where(down_elem > 0, new_global[None, :], client_flats)
    if mix_alpha > 0:
        new_flats = mix_alpha * client_flats + (1 - mix_alpha) * new_flats
    return new_global, new_flats


@functools.partial(jax.jit,
                   static_argnames=("mode", "payload", "n_params",
                                    "use_pallas", "block_slots",
                                    "block_pkts", "mix_alpha", "interpret",
                                    "agg_clip", "clip_tau", "axes", "mesh"),
                   donate_argnums=(0, 1))
def _round_device(total, counts, sched_idx, sched_w, sched_pk, sched_src,
                  sched_scales, prev_global, client_flats, down_mask, *,
                  mode: str,
                  payload: int, n_params: int, use_pallas: bool,
                  block_slots: int, block_pkts: int, mix_alpha: float,
                  interpret: bool, agg_clip: bool = False,
                  clip_tau: float = 1.0, axes: tuple = (), mesh=None):
    """The whole round as one compiled dataflow.

    total (S, W) / counts (S,) are donated and carried through the drain
    scan in place; the END divide + per-slot fallback (the exact op
    sequence of ``StreamingAggregator.finalize`` + ``finalize_round``)
    and — when ``client_flats``/``down_mask`` are present — the TX
    downlink fallback run fused in the same call.

    ``sched_pk`` holds the shipped payload rows and ``sched_src`` the
    row each schedule cell folds; each leaf expands its own on the
    device (``_fold_leaf``).  On the q8 wire path ``sched_pk`` is int8
    and ``sched_scales`` carries the per-packet dequant scales;
    dequantization happens inside the scan body (DESIGN.md §9), so the
    round's only f32 uplink form is the accumulator itself.

    ``axes`` names the mesh axes the schedule arrays lead with (none on
    one chip); ``fold_leaves`` folds each leaf into leaf-local partials
    and combines them with one psum per level (DESIGN.md §7, §12) —
    over ``mesh`` when given, else emulated on one device; the END
    divide below is fused after the combine either way.
    """
    S = counts.shape[0]
    acc, cnt = total, counts[:, None]
    pad = (-S) % block_slots if use_pallas else 0
    if pad:
        acc = jnp.pad(acc, ((0, pad), (0, 0)))
        cnt = jnp.pad(cnt, ((0, pad), (0, 0)))
    weigh = None
    if agg_clip:
        # norm_clip mode (§11): bound each packet's influence before the
        # fold, elementwise per packet, so the numerics match the eager
        # drain on any topology
        def weigh(ops, rows):
            return norm_clip_weights(ops.weights, rows, tau=clip_tau,
                                     scales=ops.scales)
    leaf = functools.partial(
        _fold_leaf, exact=(mode == "exact"), use_pallas=use_pallas,
        block_slots=block_slots, block_pkts=block_pkts,
        interpret=interpret, weigh=weigh)
    ops = Leaves(sched_idx, sched_w, sched_pk, sched_src, sched_scales,
                 None)
    acc, cnt = fold_leaves(leaf, ops, acc, cnt, axes=axes, mesh=mesh)
    total, counts = acc[:S], cnt[:S, 0]
    new_global, new_flats = _publish(
        _mean(total, counts), counts > 0, prev_global, client_flats,
        down_mask, payload=payload, n_params=n_params, mix_alpha=mix_alpha)
    return total, counts, new_global, new_flats


@functools.partial(jax.jit,
                   static_argnames=("payload", "n_params", "n_slots",
                                    "n_clients", "use_pallas",
                                    "block_slots", "block_pkts",
                                    "mix_alpha", "interpret", "median",
                                    "beta", "axes", "mesh"))
def _robust_round_device(sched_idx, sched_w, sched_pk, sched_src,
                         sched_scales, prev_global, client_flats, down_mask,
                         *,
                         payload: int, n_params: int, n_slots: int,
                         n_clients: int, use_pallas: bool,
                         block_slots: int, block_pkts: int,
                         mix_alpha: float, interpret: bool, median: bool,
                         beta: float, axes: tuple = (), mesh=None):
    """Robust table round (trimmed-mean / median, DESIGN.md §11) as one
    compiled dataflow.

    The schedule arrives with the *combined index* ``slot·K + client``
    and presence weight 1.0 per accepted packet, so the unchanged
    scatter kernels fold it into an ``(S·K, W)`` accumulator that IS
    the per-slot client table: each (slot, client) row is written
    exactly once (dedup upstream), so ``0 + 1.0·row`` reproduces the
    eager engine's direct table assignment bitwise (q8 rows dequantize
    in-body as ever).  The fold always runs exact — approx mode's
    last-writer-wins window cannot race rows that never collide — and
    on a mesh each row lives on one leaf, so the psums add it to zeros:
    bitwise at any factorization on ANY payloads (DESIGN.md §12).  The
    reshaped table feeds the fused rank-select finalize; the per-slot
    contributor count ``m`` replaces the mean path's ``counts`` (same
    fallback semantics), and the TX downlink fuses in as usual.  The
    schedule arrays and ``axes`` are read as in ``_round_device``.

    No donation: the carried ``(S, W)`` accumulators are the wrong
    shape for the table; the returned ``total`` is the table's per-slot
    sum ``Σ_c`` so the engine's carry keeps its meaning.
    """
    S, K = n_slots, n_clients
    SK = S * K
    # +1 dustbin row for the flat scatter's idx=-1 padding on the jnp
    # body; pallas pads the table to whole slot blocks
    pad = (-SK) % block_slots if use_pallas else 1
    cnt = jnp.zeros((SK + pad, 1), jnp.float32)
    acc = jnp.zeros((SK + pad, payload), jnp.float32)
    leaf = functools.partial(
        _fold_leaf, exact=True, use_pallas=use_pallas,
        block_slots=block_slots, block_pkts=block_pkts,
        interpret=interpret, table=True)
    ops = Leaves(sched_idx, sched_w, sched_pk, sched_src, sched_scales,
                 None)
    acc, cnt = fold_leaves(leaf, ops, acc, cnt, axes=axes, mesh=mesh)
    table = acc[:SK].reshape(S, K, payload)
    pres = cnt[:SK, 0].reshape(S, K)
    if use_pallas:
        spad = (-S) % block_slots
        agg, m = robust_finalize_pallas(
            jnp.pad(table, ((0, spad), (0, 0), (0, 0))),
            jnp.pad(pres, ((0, spad), (0, 0))),
            median=median, beta=beta, block_slots=block_slots,
            interpret=interpret)
        agg, m = agg[:S], m[:S]
    else:
        agg, m = robust_finalize_jnp(table, pres, median=median, beta=beta)
    total = jnp.sum(table, axis=1)                        # (S, W)
    new_global, new_flats = _publish(
        agg, m > 0, prev_global, client_flats, down_mask, payload=payload,
        n_params=n_params, mix_alpha=mix_alpha)
    return total, m, new_global, new_flats


def _combined_table_sched(sched: DrainSchedule,
                          n_clients: int) -> DrainSchedule:
    """Rewrite a drain schedule for the robust table fold (§11): slot
    index -> combined ``slot·K + client`` index, per-arrival FedAvg
    weight -> presence weight 1.0 (rank statistics are unweighted).
    Batch composition — and hence shard ownership — is untouched, so
    ``leaf_layout``'s shard split applies downstream unchanged."""
    assert sched.clients is not None, \
        "robust table modes need a client-tracked schedule"
    valid = sched.idx >= 0
    idx2 = np.where(valid,
                    sched.idx.astype(np.int64) * n_clients
                    + sched.clients.astype(np.int64),
                    -1).astype(np.int32)
    return dataclasses.replace(sched, idx=idx2,
                               weights=valid.astype(np.float32))


def dispatch_round(cfg: EngineConfig, sched: DrainSchedule, total, counts,
                   prev_global, client_flats=None, down_mask=None,
                   mix_alpha: float = 0.0):
    """Dispatch one round (async) -> (total', counts', new_global,
    new_flats|None).  ``total``/``counts`` are donated — callers pass
    buffers they own and adopt the returned ones.

    ``cfg.shards > 1`` and ``cfg.hosts > 1`` lay the schedule out per
    leaf (``leaf_layout``) and fold it over a real ``'worker'`` or
    ``('host', 'worker')`` mesh when the platform has enough devices
    (``runtime.sharding``), else — on the CPU only — over a bitwise
    single-device emulation (DESIGN.md §7, §12).
    """
    fn, args, kwargs = _round_call(cfg, sched, total, counts, prev_global,
                                   client_flats, down_mask, mix_alpha)
    with _span("fl.launch"):
        return fn(*args, **kwargs)


def lower_round(cfg: EngineConfig, sched: DrainSchedule, total, counts,
                prev_global, client_flats=None, down_mask=None,
                mix_alpha: float = 0.0) -> jax.stages.Lowered:
    """The round ``dispatch_round`` would run, lowered and not run: its
    text shows which fold body and which collectives the round holds
    (a Pallas kernel lowers to ``tpu_custom_call``, a mesh combine to
    an all-reduce)."""
    fn, args, kwargs = _round_call(cfg, sched, total, counts, prev_global,
                                   client_flats, down_mask, mix_alpha)
    return fn.lower(*args, **kwargs)


def _round_call(cfg: EngineConfig, sched: DrainSchedule, total, counts,
                prev_global, client_flats, down_mask, mix_alpha: float):
    """(jitted round, args, static kwargs) for one round's schedule."""
    if cfg.mode not in ("exact", "approx"):
        raise ValueError(cfg.mode)
    robust_table = cfg.agg_mode in TABLE_MODES
    axes, _, mesh = _topology(cfg)
    idx, w, pk, src, sc, _ = (None if a is None else a[0]
                              for a in leaf_layout(cfg, [sched]))

    def opt(a):
        return None if a is None else jnp.asarray(a)

    with _span("fl.h2d"):
        # the table modes' round builds its own accumulator
        args = () if robust_table else (jnp.asarray(total, jnp.float32),
                                        jnp.asarray(counts, jnp.float32))
        args += (jnp.asarray(idx), jnp.asarray(w), jnp.asarray(pk),
                 jnp.asarray(src), opt(sc), jnp.asarray(prev_global),
                 opt(client_flats), opt(down_mask))
    common = dict(payload=cfg.payload, n_params=cfg.n_params,
                  use_pallas=_use_pallas(cfg), block_slots=8,
                  block_pkts=min(BLOCK_PKTS, idx.shape[-1]),
                  mix_alpha=float(mix_alpha), interpret=_interpret(),
                  axes=axes, mesh=mesh)
    if robust_table:
        return _robust_round_device, args, dict(
            common, n_slots=cfg.n_slots, n_clients=cfg.n_clients,
            median=(cfg.agg_mode == "median"), beta=float(cfg.trim_beta))
    return _round_device, args, dict(
        common, mode=cfg.mode, agg_clip=(cfg.agg_mode == "norm_clip"),
        clip_tau=float(cfg.clip_tau))


# ---------------------------------------------------------------------------
# Drivers: single round, and double-buffered multi-round overlap
# ---------------------------------------------------------------------------

def _accumulators(cfg: EngineConfig):
    """The zeroed ``(S, W)`` sum and ``(S,)`` counts a mean-family
    round folds into and donates; ``(None, None)`` for the table modes,
    whose round builds its own client table."""
    if cfg.agg_mode in TABLE_MODES:
        return None, None
    return (jnp.zeros((cfg.n_slots, cfg.payload), jnp.float32),
            jnp.zeros((cfg.n_slots,), jnp.float32))


def run_compiled_round(cfg: EngineConfig, client_flats, prev_global,
                       events: Iterable, down_mask=None, weights=None,
                       mix_alpha: float = 0.0) -> RoundResult:
    """Compiled counterpart of ``server.run_engine_round``: bulk demux,
    then exactly one device dispatch for drains + END + TX.  The call
    runs in the ``fl.round`` span (``SPANS``)."""
    with _span("fl.round"):
        sched, stats, up = demux_events(cfg, events, weights)
        with _span("fl.launch"):
            total, counts = _accumulators(cfg)
        _, counts, new_global, new_flats = dispatch_round(
            cfg, sched, total, counts, prev_global,
            client_flats=None if down_mask is None else client_flats,
            down_mask=down_mask, mix_alpha=mix_alpha)
        with _span("fl.h2d"):
            up = jnp.asarray(up)
        return RoundResult(new_global, counts, up, new_flats, stats)


def run_compiled_rounds(cfg: EngineConfig, rounds: Iterable,
                        prev_global, *, weights=None,
                        mix_alpha: float = 0.0) -> List[RoundResult]:
    """Double-buffered multi-round driver (the paper's pipelined cores).

    ``rounds`` yields ``(events, client_flats, down_mask)`` per round
    (``client_flats``/``down_mask`` may be None).  Round r is dispatched
    asynchronously and, while the device executes its scan, round r+1's
    demux runs on the host; each round's ``prev_global`` chains from the
    previous round's device-resident ``new_global`` without a host
    round-trip.  Results are materialized one round behind dispatch.
    """
    results: List[RoundResult] = []
    prev = jnp.asarray(prev_global)
    pending: Optional[RoundResult] = None
    for events, client_flats, down_mask in rounds:
        try:
            sched, stats, up = demux_events(cfg, events, weights)
        except QuorumError as e:
            # a continuously serving loop must not lose the rounds it
            # already served because one round missed quorum: flush the
            # in-flight round and hand the completed results to the
            # caller on the exception
            if pending is not None:
                # staticcheck: allow(hostsync) — overlap-driver barrier: the in-flight round must materialize before the QuorumError escapes with its results
                pending.new_global.block_until_ready()
                results.append(pending)
            e.results = results
            raise
        if pending is not None:       # round r-1 ran while we demuxed
            # staticcheck: allow(hostsync) — overlap-driver barrier: round r-1 is collected only after round r's demux, preserving the double-buffered overlap (DESIGN.md §3)
            pending.new_global.block_until_ready()
            results.append(pending)
        total, counts = _accumulators(cfg)
        _, counts, new_global, new_flats = dispatch_round(
            cfg, sched, total, counts, prev,
            client_flats=None if down_mask is None else client_flats,
            down_mask=down_mask, mix_alpha=mix_alpha)
        pending = RoundResult(new_global, counts, jnp.asarray(up),
                              new_flats, stats)
        prev = new_global
    if pending is not None:
        # staticcheck: allow(hostsync) — overlap-driver barrier: final flush of the last in-flight round after the input stream is exhausted
        pending.new_global.block_until_ready()
        results.append(pending)
    return results


# ---------------------------------------------------------------------------
# Async buffered mode (FedBuff) — compiled path (DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AsyncSchedule:
    """The per-window drain schedules of one async demux call.

    Window ``w`` holds the packets of the updates folded between emit
    boundaries ``w-1`` and ``w``; every window is independently
    ring-demuxed (``build_drain_schedule`` — rings and the rr pointer
    reset at each emit, exactly like the eager twin), and
    ``leaf_layout`` pads the windows to a common row count and stacks
    them, so the whole call scans as one ``lax.scan`` over windows.
    ``emit[w]`` marks windows that close a full buffer (the divide fires
    and the accumulator resets); a trailing non-emit window carries the
    residual (< B) folds into the returned ``AsyncState``.
    """
    scheds: List[DrainSchedule]
    emit: np.ndarray        # (n_windows,) bool — divide + reset fires
    n_emits: int
    pending_after: int      # updates folded past the last emit

    @property
    def n_windows(self) -> int:
        return len(self.scheds)


def demux_events_async(cfg: EngineConfig, events: Iterable,
                       weights: Optional[np.ndarray] = None, *,
                       base_version: int = 0, base_pending: int = 0
                       ) -> Tuple[AsyncSchedule, AsyncStats,
                                  List[UpdateRecord]]:
    """Bulk async RX: one pass over ``(Packet, payload)`` events with
    the session grammar of ``server.AsyncServerEngine.rx``, then one
    ring demux per emit window -> (schedule, stats, update log).

    Sessions (START ... DATA ... END) interleave freely and repeat per
    client; DATA is accepted iff its client's session is open and the
    slot is unseen *in that session*; an accepted END folds the
    session's packets into the current window with staleness
    ``(base_version + emits_so_far) - version_at_send`` (clamped >= 0,
    version-at-send from the session's START tag).  Every
    ``cfg.buffer_size`` folds close a window with ``emit=True``;
    ``base_pending`` updates carried from a previous call count toward
    the first window's budget.  Sessions still open at stream end are
    in-flight: buffered this call, neither folded nor carried.
    """
    if cfg.buffer_size is None:
        raise ValueError("async demux needs cfg.buffer_size")
    K = cfg.n_clients
    wts = (np.ones(K, np.float32) if weights is None
           else np.asarray(weights, np.float32))
    stats = AsyncStats()
    updates: List[UpdateRecord] = []
    up = [False] * K
    sess = [-1] * K
    ver = [0] * K
    seen: List[set] = [set() for _ in range(K)]
    buf: List[list] = [[] for _ in range(K)]
    windows: List[list] = []
    emit_flags: List[bool] = []
    win: List[tuple] = []
    pending = base_pending
    emits = 0
    data_k, start_k, end_k = Kind.DATA, Kind.START, Kind.END
    for packet, payload in events:
        kind = packet.kind
        c = packet.client
        if kind is data_k:
            if payload_malformed(payload, packet.wire_dtype != "f32",
                                 packet.scale):
                stats.malformed_dropped += 1
                continue
            if not up[c]:
                stats.phase_dropped += 1
                continue
            slot = packet.index
            if slot in seen[c]:
                stats.duplicates_dropped += 1
                continue
            seen[c].add(slot)
            buf[c].append((slot, payload, packet.wire_dtype != "f32",
                           packet.scale))
            stats.data_enqueued += 1
        elif kind is start_k:
            stats.control_replies += 1
            if not up[c]:
                up[c] = True
                sess[c] += 1
                ver[c] = int(packet.version)
                seen[c] = set()
                buf[c] = []
        elif kind is end_k:
            stats.control_replies += 1
            if not up[c]:
                continue                      # dup / late END: grace-acked
            up[c] = False
            fold_version = base_version + emits
            staleness = max(0, fold_version - ver[c])
            updates.append(UpdateRecord(c, sess[c], ver[c], fold_version,
                                        staleness, len(buf[c]), emits))
            stats.updates_accepted += 1
            h = stats.staleness_hist
            h[staleness] = h.get(staleness, 0) + 1
            base_w = float(wts[c])
            for slot, pay, q8, sc in buf[c]:
                win.append((slot, base_w, staleness, pay, q8, sc, c))
            buf[c] = []
            pending += 1
            if pending >= cfg.buffer_size:
                windows.append(win)
                emit_flags.append(True)
                win = []
                pending = 0
                emits += 1
    if win:           # residual folds ride a trailing non-emit window
        windows.append(win)
        emit_flags.append(False)
    for c in range(K):
        if up[c]:
            stats.updates_in_flight += 1
            stats.data_in_flight += len(buf[c])
    stats.emits = emits
    # wire tri-state decided over ALL folded packets, so every window's
    # payload block shares one dtype (same rule as the sync demux §9)
    n_pkts = sum(len(w) for w in windows)
    n_q8 = sum(e[4] for w in windows for e in w)
    homogeneous_q8 = n_pkts > 0 and n_q8 == n_pkts

    def _window_sched(entries: list) -> DrainSchedule:
        n = len(entries)
        slots = np.asarray([e[0] for e in entries], np.int32)
        w_col = np.asarray([e[1] for e in entries], np.float32)
        st_col = np.asarray([e[2] for e in entries], np.float32)
        cl_col = np.asarray([e[6] for e in entries], np.int32)
        sc_col = None
        if homogeneous_q8:
            pay = (np.asarray([e[3] for e in entries], np.int8) if n
                   else np.zeros((0, cfg.payload), np.int8))
            sc_col = np.asarray([e[5] for e in entries], np.float32)
        elif n_q8 == 0:
            pay = (np.asarray([e[3] for e in entries], np.float32) if n
                   else np.zeros((0, cfg.payload), np.float32))
        else:     # mixed wire: host-decode the q8 rows (DESIGN.md §9)
            pay = (np.stack([
                np.asarray(p, np.int8).astype(np.float32) * np.float32(s)
                if q else np.asarray(p, np.float32)
                for _, _, _, p, q, s, _ in entries]) if n
                else np.zeros((0, cfg.payload), np.float32))
        return build_drain_schedule(
            slots, w_col, pay, n_workers=cfg.n_workers,
            ring_capacity=cfg.ring_capacity, ring_assign=cfg.ring_assign,
            scales=sc_col, staleness=st_col, clients=cl_col)

    scheds = [_window_sched(w) for w in windows]
    stats.batches_drained = sum(s.n_batches for s in scheds)
    asched = AsyncSchedule(scheds, np.asarray(emit_flags, bool), emits,
                           pending)
    return asched, stats, updates


@functools.partial(jax.jit,
                   static_argnames=("mode", "payload", "n_params",
                                    "use_pallas", "block_slots",
                                    "block_pkts", "interpret",
                                    "stale_mode", "stale_alpha",
                                    "norm_clip", "agg_clip", "clip_tau",
                                    "axes", "mesh"),
                   donate_argnums=(0, 1))
def _async_device(total, counts, g, ops: Leaves, emit, *, mode: str,
                  payload: int, n_params: int, use_pallas: bool,
                  block_slots: int, block_pkts: int, interpret: bool,
                  stale_mode: str, stale_alpha: float, norm_clip: float,
                  agg_clip: bool = False, clip_tau: float = 1.0,
                  axes: tuple = (), mesh=None):
    """One jitted dispatch for a whole async demux call (DESIGN.md §10).

    ``lax.scan`` over emit windows (``ops`` leads with the window axis,
    then the leaf axes ``axes``) with the donated ``(total, counts)``
    accumulators and the live global carried in place.  Each window
    step: the staleness weighting (``staleness_weights`` — applied
    in-body, so the q8 wire's norm screening sees the dequantized rows
    without ever materializing them) rescales the window's base
    weights, the window's drain rows fold through the same leaf fold as
    a synchronous round, and — where ``emit`` is set — the END divide +
    per-slot fallback publishes a new global and zeroes the
    accumulators for the next buffer.  Non-emit windows (the residual
    tail) fold and carry.  Per-window outputs: the live global after
    the window and the pre-reset per-slot counts.
    """
    S = counts.shape[0]
    acc, cnt = total, counts[:, None]
    pad = (-S) % block_slots if use_pallas else 0
    if pad:
        acc = jnp.pad(acc, ((0, pad), (0, 0)))
        cnt = jnp.pad(cnt, ((0, pad), (0, 0)))

    def weigh(ops, rows):
        eff = staleness_weights(ops.weights, ops.staleness, rows=rows,
                                scales=ops.scales, mode=stale_mode,
                                alpha=stale_alpha, norm_clip=norm_clip)
        if agg_clip:
            # agg_mode="norm_clip" composes *after* the staleness
            # weighting, matching the eager _fold_window (§11)
            eff = norm_clip_weights(eff, rows, tau=clip_tau,
                                    scales=ops.scales)
        return eff

    leaf = functools.partial(
        _fold_leaf, exact=(mode == "exact"), use_pallas=use_pallas,
        block_slots=block_slots, block_pkts=block_pkts,
        interpret=interpret, weigh=weigh)

    def step(carry, xs):
        acc, cnt, g = carry
        window, em = xs
        acc, cnt = fold_leaves(leaf, window, acc, cnt, axes=axes, mesh=mesh)
        counts_live = cnt[:S, 0]
        # the emit: the exact op sequence of the synchronous END
        cand, _ = _publish(_mean(acc[:S], counts_live), counts_live > 0, g,
                           None, None, payload=payload, n_params=n_params,
                           mix_alpha=0.0)
        new_g = jnp.where(em, cand, g)
        acc = jnp.where(em, jnp.zeros_like(acc), acc)
        cnt = jnp.where(em, jnp.zeros_like(cnt), cnt)
        return (acc, cnt, new_g), (new_g, counts_live)

    (acc, cnt, g), (gs, cs) = jax.lax.scan(step, (acc, cnt, g), (ops, emit))
    return acc[:S], cnt[:S, 0], g, gs, cs


def dispatch_async(cfg: EngineConfig, asched: AsyncSchedule, total, counts,
                   prev_global):
    """Dispatch one async demux call -> (total', counts', final_global,
    per-window globals (n_windows, P), per-window counts (n_windows, N)).

    ``total``/``counts`` are donated.  Every window is laid out per leaf
    as a synchronous round is (``leaf_layout``: host partition, then
    shard split) and folds over the same mesh or its bitwise emulation
    (DESIGN.md §7, §12).
    """
    axes, _, mesh = _topology(cfg)
    ops = leaf_layout(cfg, asched.scheds)
    return _async_device(
        jnp.asarray(total, jnp.float32), jnp.asarray(counts, jnp.float32),
        jnp.asarray(prev_global, jnp.float32),
        jax.tree.map(jnp.asarray, ops), jnp.asarray(asched.emit),
        mode=cfg.mode, payload=cfg.payload, n_params=cfg.n_params,
        use_pallas=_use_pallas(cfg), block_slots=8,
        block_pkts=min(BLOCK_PKTS, ops.idx.shape[-1]),
        interpret=_interpret(), stale_mode=cfg.staleness_mode,
        stale_alpha=float(cfg.staleness_alpha),
        norm_clip=float(cfg.norm_clip),
        agg_clip=(cfg.agg_mode == "norm_clip"),
        clip_tau=float(cfg.clip_tau), axes=axes, mesh=mesh)


def run_compiled_async(cfg: EngineConfig, events: Iterable, prev_global,
                       *, weights=None,
                       state: Optional[AsyncState] = None) -> AsyncResult:
    """Compiled counterpart of ``server.run_async_engine``: one host
    demux pass over the stream, then exactly one device dispatch for
    every window's fold and every emit's divide (DESIGN.md §10).

    ``state`` carries the residual accumulator, version and pending
    count from a previous call; its buffers are copied before the
    donated dispatch, so the caller's state stays readable.
    """
    if state is None:
        state = AsyncState.init(cfg, prev_global)
    asched, stats, updates = demux_events_async(
        cfg, events, weights, base_version=state.version,
        base_pending=state.pending)
    g0 = jnp.asarray(state.global_, jnp.float32)
    if asched.n_windows == 0:
        new_state = AsyncState(jnp.asarray(state.total, jnp.float32),
                               jnp.asarray(state.counts, jnp.float32),
                               g0, state.version, asched.pending_after)
        P = cfg.n_params
        return AsyncResult(jnp.zeros((0, P), jnp.float32),
                           jnp.zeros((0, cfg.n_slots), jnp.float32),
                           new_state, stats, updates)
    total = jnp.array(state.total, jnp.float32, copy=True)
    counts = jnp.array(state.counts, jnp.float32, copy=True)
    total, counts, g, gs, cs = dispatch_async(cfg, asched, total, counts,
                                              g0)
    em = np.nonzero(asched.emit)[0]
    new_state = AsyncState(total, counts, g,
                           state.version + asched.n_emits,
                           asched.pending_after)
    return AsyncResult(gs[em], cs[em], new_state, stats, updates)
