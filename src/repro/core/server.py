"""Ring-buffered packet-path server round engine (paper §3.2, §4).

The paper's DPDK server is a three-stage pipeline: one RX core polls the
NIC and demultiplexes packets onto per-worker rings, N worker cores
drain their rings and add payloads into a shared accumulator, and a TX
core streams the averaged global parameters back out.  ``ServerEngine``
is the executable counterpart of that pipeline for this repo: it
consumes an interleaved multi-client stream of ``core.protocol.Packet``
events — lossy, out-of-order, duplicated — and drives the device-side
scatter-accumulate (kernels/packet_scatter.py) through a
``StreamingAggregator`` once per drained ring batch.

Semantics (DESIGN.md §3):

- **RX** answers control packets through the per-round ``ServerFSM`` and
  deduplicates DATA packets against the FSM's uplink sets (UDP may
  duplicate; the wire index makes re-delivery idempotent), so the
  engine's per-slot arrival counts equal the protocol-level counts for
  *any* loss/duplication pattern.
- **Workers** drain a ring when it reaches capacity; each drained batch
  is one scatter-accumulate call.  ``mode="exact"`` adds every arrival
  (the locked server); ``mode="approx"`` is the paper's lock-free race
  made deterministic — within a batch the last writer to a slot wins and
  the ring capacity is the race window.
- **END** triggers the count-normalized divide (the existing
  ``StreamingAggregator.finalize``), with per-packet fallback to the
  previous global for slots nobody delivered (§3.2.2) — bitwise the same
  dataflow as ``aggregation.fused_round_step``.
- **TX** applies the downlink mask with the client-side fallback (§3.1):
  elements of packets lost on the way down stay at the client's local
  value.

This module is the *eager reference*: every compiled path
(core/engine_compiled.py) is differential-tested against an engine
here.  The invariants the twins pin down:

- **Bitwise parity**: with integer-valued payloads in exact mode, the
  compiled round — at any ``(hosts, shards)`` — equals this engine bit
  for bit; approx mode equals the engine with the same batching
  (``run_hier_round`` builds the per-host eager twin for
  ``hosts > 1``, DESIGN.md §12).
- **Conservation**: every DATA packet lands in exactly one bucket
  (``data_enqueued`` + ``duplicates_dropped`` + ``phase_dropped`` +
  ``late_dropped`` + ``malformed_dropped``), and accepted arrivals
  equal the protocol-level counts for any loss/duplication pattern.
- **Close semantics**: deadline → straggler timeout → quorum guard
  fire in that order at every close, with identical wording from the
  eager and bulk paths (``check_quorum``).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import expand_packet_mask
from repro.core.packets import PacketizedShape, depacketize
from repro.core.pipeline import StreamingAggregator
from repro.core.protocol import Kind, Packet, ServerFSM, ServerPhase


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shape + pipeline topology of one server round (paper Table 1)."""
    n_clients: int
    n_params: int
    payload: int                       # floats per packet (wire: 367)
    n_workers: int = 5                 # paper: 1 RX + 5 workers + 1 TX
    ring_capacity: int = 64            # worker ring depth == race window
    mode: str = "exact"                # exact | approx
    ring_assign: str = "rr"            # rr | slot (see ServerEngine.rx)
    use_kernel: bool = True            # False: sequential host oracle
    compile: bool = False              # True: one lax.scan per round
    scan_body: str = "auto"            # auto | pallas | jnp (compile=True)
    # deadline-closed partial rounds (DESIGN.md §8): the round's uplink
    # barrier closes after this many rx events — clients still short of
    # their END are TIMED_OUT (their delivered packets count, their
    # undelivered ones become wire losses) and later DATA is dropped as
    # ``late_dropped``.  None: the barrier only closes at finalize.
    round_deadline: Optional[int] = None
    # quorum guard: finalizing a round with fewer clients past their
    # uplink END than this raises instead of publishing a global built
    # from too few contributions.  0 disables the guard.
    min_clients: int = 0
    # worker-mesh shards for the compiled round (DESIGN.md §7): each
    # shard folds its worker rings' drains into a per-shard partial sum
    # combined at END — the paper's per-core layout.  Effective device
    # parallelism is min(shards, n_workers); a CPU with fewer devices
    # runs the fold emulated, an accelerator with fewer raises.  Any
    # shard count is bitwise identical on integer payloads.
    shards: int = 1
    # hierarchical leaf hosts for the compiled round (DESIGN.md §12):
    # each host owns a contiguous client range, demuxes only its own
    # clients' packets with its own rings, and the fold combines with
    # one psum per level of the 2-D ('host', 'worker') mesh.  Any
    # (hosts, shards) factorization is bitwise identical to hosts=1 on
    # integer payloads in exact mode; approx mode matches the eager
    # per-host twin (run_hier_round) instead, because per-host rings
    # change batch composition and with it the race windows.
    hosts: int = 1
    # async buffered mode (DESIGN.md §10): with ``buffer_size = B`` the
    # engine stops framing rounds at END/deadline — accepted client
    # updates fold continuously into the donated accumulators and a new
    # global is emitted every B accepted updates.  Staleness
    # (version-at-fold − version-at-send, from the wire version tag) is
    # weighted by ``staleness_mode``: const (FedBuff unweighted), poly
    # ((1+s)^-alpha decay), or norm (poly × FedNS-style norm screening
    # with threshold ``norm_clip``).  None: synchronous rounds.
    buffer_size: Optional[int] = None
    staleness_mode: str = "const"      # const | poly | norm
    staleness_alpha: float = 0.5       # poly/norm decay exponent
    norm_clip: float = 1.0             # norm-mode screening threshold
    # Byzantine-robust finalize (DESIGN.md §11): how the accumulated
    # per-slot statistics become the new global at END.  ``mean`` is the
    # paper's count-normalized divide (bitwise the pre-§11 engine);
    # ``trimmed_mean`` / ``median`` fold the round into a per-slot
    # (K, W) client table and take coordinate-wise order statistics
    # over the contributors (breakdown points floor(beta·m) and
    # ceil(m/2)-1 respectively); ``norm_clip`` keeps the cheap
    # (total, counts) path and rescales every packet's weight by
    # ``clip_tau / max(clip_tau, ‖row‖₂)``, bounding any one client's
    # influence.  In async buffered mode ``norm_clip`` composes with
    # ``staleness_mode`` (the clip applies on top of the staleness
    # decay); the table modes need the synchronous round barrier.
    agg_mode: str = "mean"             # mean|trimmed_mean|median|norm_clip
    trim_beta: float = 0.1             # trimmed_mean: fraction per side
    clip_tau: float = 1.0              # norm_clip influence bound

    def __post_init__(self):
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.round_deadline is not None and self.round_deadline < 0:
            raise ValueError(
                f"round_deadline must be >= 0, got {self.round_deadline}")
        if not 0 <= self.min_clients <= self.n_clients:
            raise ValueError(
                f"min_clients must be in [0, n_clients], got "
                f"{self.min_clients}")
        if self.shards > 1 and not self.compile:
            raise ValueError(
                "shards > 1 requires compile=True: sharding demuxes the "
                "compiled drain schedule over the worker mesh "
                "(DESIGN.md §7)")
        if self.hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {self.hosts}")
        if self.hosts > 1 and not self.compile:
            raise ValueError(
                "hosts > 1 requires compile=True: the hierarchical round "
                "partitions the compiled drain schedule over the "
                "(host, worker) mesh (DESIGN.md §12); the eager per-host "
                "twin is server.run_hier_round")
        if self.staleness_mode not in ("const", "poly", "norm"):
            raise ValueError(
                f"staleness_mode must be const|poly|norm, got "
                f"{self.staleness_mode!r}")
        if self.staleness_alpha < 0:
            raise ValueError(
                f"staleness_alpha must be >= 0, got {self.staleness_alpha}")
        if self.norm_clip <= 0:
            raise ValueError(
                f"norm_clip must be > 0, got {self.norm_clip}")
        if self.agg_mode not in ("mean", "trimmed_mean", "median",
                                 "norm_clip"):
            raise ValueError(
                f"agg_mode must be mean|trimmed_mean|median|norm_clip, "
                f"got {self.agg_mode!r}")
        if not 0.0 <= self.trim_beta < 0.5:
            raise ValueError(
                f"trim_beta must be in [0, 0.5) (trimming half the "
                f"contributors from each side leaves nothing), got "
                f"{self.trim_beta}")
        if self.clip_tau <= 0:
            raise ValueError(
                f"clip_tau must be > 0, got {self.clip_tau}")
        if self.buffer_size is not None:
            if self.buffer_size < 1:
                raise ValueError(
                    f"buffer_size must be >= 1, got {self.buffer_size}")
            if self.round_deadline is not None or self.min_clients:
                raise ValueError(
                    "async buffered mode has no round barrier: "
                    "round_deadline / min_clients do not apply "
                    "(DESIGN.md §10)")
            if self.agg_mode in ("trimmed_mean", "median"):
                raise ValueError(
                    "trimmed_mean/median need the synchronous round's "
                    "per-slot client table; async buffered mode "
                    "supports agg_mode mean|norm_clip (DESIGN.md §11)")

    @property
    def n_slots(self) -> int:
        return PacketizedShape(self.n_params, self.payload).n_packets


@dataclasses.dataclass
class EngineStats:
    data_enqueued: int = 0             # unique DATA packets ringed
    duplicates_dropped: int = 0        # RX-level dedup hits (same slot again)
    phase_dropped: int = 0             # DATA outside START..END framing
    batches_drained: int = 0           # scatter-accumulate calls
    control_replies: int = 0           # START_ACK / END_ACK emitted
    stragglers_timed_out: int = 0      # clients short of END at round close
    late_dropped: int = 0              # DATA arriving past the deadline
    malformed_dropped: int = 0         # non-finite payload / bad q8 scale


def payload_malformed(payload, wire_q8: bool, scale: float) -> bool:
    """Wire-boundary hardening (DESIGN.md §11): is this DATA packet
    poison?  An f32 payload with any non-finite element (NaN/Inf), or a
    q8 packet whose dequant scale is zero, negative, or non-finite
    (int8 payload bytes are finite by construction), would permanently
    corrupt the donated accumulators — one NaN survives every
    subsequent add and divide.  Both RX paths (eager per-packet, bulk
    demux) drop such packets *before* the dedup set records the slot,
    so a clean retransmission of the same (client, slot) is still
    accepted; drops are counted in ``EngineStats.malformed_dropped``.
    A DATA packet legally carrying no payload (it will be phase- or
    late-dropped) is not malformed.
    """
    if wire_q8:
        return not (np.isfinite(scale) and scale > 0)
    if payload is None:
        return False
    return not bool(np.all(np.isfinite(np.asarray(payload, np.float32))))


class QuorumError(RuntimeError):
    """Round closed with fewer participants than ``min_clients``."""


def check_quorum(participants: int, min_clients: int,
                 stragglers: int) -> None:
    """Shared quorum guard: the eager close and the compiled bulk demux
    must report the same verdict, in the same words, for one round."""
    if participants < min_clients:
        raise QuorumError(
            f"round closed with {participants} participant(s) < "
            f"min_clients={min_clients} ({stragglers} timed out)")


@dataclasses.dataclass
class RoundResult:
    new_global: jnp.ndarray            # (P,) count-normalized global
    counts: jnp.ndarray                # (N,) per-slot weighted arrivals
    up_mask: jnp.ndarray               # (K, N) deduplicated arrival mask
    new_client_flats: Optional[jnp.ndarray]   # (K, P) after downlink
    stats: EngineStats


class ServerEngine:
    """One round of the RX → N-worker → TX pipeline.

    Feed packets with :meth:`rx` (payload rows ride alongside DATA
    packets — the 4-byte wire index is ``Packet.index``), then
    :meth:`finalize_round` runs the END divide and :meth:`distribute`
    the TX/downlink step.
    """

    def __init__(self, cfg: EngineConfig,
                 weights: Optional[jnp.ndarray] = None):
        self.cfg = cfg
        self.fsm = ServerFSM(cfg.n_clients, cfg.n_slots)
        self.agg = StreamingAggregator(cfg.n_slots, cfg.payload,
                                       use_kernel=cfg.use_kernel)
        self.weights = (np.ones(cfg.n_clients, np.float32) if weights is None
                        else np.asarray(weights, np.float32))
        # per-worker rings of (slot, weight, payload-row).  ``rr`` demux
        # (default) spreads arrivals round-robin like the paper's RX
        # core, so same-slot packets rarely share a drain batch and the
        # approx-mode race stays incidental; ``slot`` demux pins every
        # slot to one worker, making same-slot collisions maximal — a
        # race stress mode, not the paper topology.
        self._rings: List[List[Tuple[int, float, np.ndarray]]] = \
            [[] for _ in range(cfg.n_workers)]
        self._rr_next = 0
        # compile=True fast path: RX records accepted arrivals with no
        # device work; the whole round runs as one compiled lax.scan at
        # END (core/engine_compiled.py, DESIGN.md §3).
        self._pend_slots: List[int] = []
        self._pend_weights: List[float] = []
        self._pend_payloads: List[np.ndarray] = []
        self._pend_q8: List[bool] = []       # wire_dtype per arrival
        self._pend_scales: List[float] = []  # q8 dequant scale (DESIGN.md §9)
        self._pend_clients: List[int] = []   # robust table row (DESIGN.md §11)
        # robust table modes (DESIGN.md §11): the eager engine keeps the
        # per-slot client table directly — one deduplicated decoded row
        # per (client, slot) — next to the ring pipeline (which still
        # runs for stats parity with the compiled schedule)
        if cfg.agg_mode in ("trimmed_mean", "median") and not cfg.compile:
            self._tab = np.zeros((cfg.n_clients, cfg.n_slots, cfg.payload),
                                 np.float32)
            self._tab_mask = np.zeros((cfg.n_clients, cfg.n_slots),
                                      np.float32)
        else:
            self._tab = None
            self._tab_mask = None
        self._events_seen = 0
        self._deadline_fired = False
        self.stats = EngineStats()

    # -- RX core --------------------------------------------------------------
    def rx(self, packet: Packet, payload=None) -> List[Packet]:
        """Process one arriving packet; returns control replies.

        DATA packets must carry their payload row (W,).  Duplicates —
        same (client, index) seen before — are dropped here, mirroring
        the set semantics of ``ServerFSM.uplink``.

        With ``cfg.round_deadline`` set, the round's uplink barrier
        closes after that many rx events: stragglers time out
        (``ServerFSM.deadline_expired``) and every later DATA packet is
        dropped and counted in ``stats.late_dropped`` — late control
        traffic still reaches the FSM, so a straggler's retransmitted
        END is grace-acked rather than ignored (DESIGN.md §8).
        """
        if (self.cfg.round_deadline is not None
                and not self._deadline_fired
                and self._events_seen >= self.cfg.round_deadline):
            self._fire_deadline()
        self._events_seen += 1
        if packet.kind != Kind.DATA:
            replies = self.fsm.on_packet(packet)
            self.stats.control_replies += len(replies)
            return replies
        if self._deadline_fired:
            self.stats.late_dropped += 1
            return []
        if payload_malformed(payload, packet.wire_dtype != "f32",
                             packet.scale):
            # dropped before the FSM and the dedup set see it, so a
            # clean retransmission of the same slot is still accepted
            self.stats.malformed_dropped += 1
            return []
        c, slot = packet.client, packet.index
        if self.fsm.phase[c] != ServerPhase.RECV_PARAMS:
            # DATA outside the START..END framing — distinct from a
            # duplicate: the FSM gate dropped it, not the dedup set.
            self.stats.phase_dropped += 1
            return []
        if slot in self.fsm.uplink[c]:
            self.stats.duplicates_dropped += 1
            return []
        assert payload is not None, "DATA packet without payload"
        self.fsm.on_packet(packet)               # records the arrival
        if self.cfg.compile:
            # record only — the drain schedule is built (and the whole
            # round dispatched) once, at finalize time; q8 payloads stay
            # int8 here so dequantization can fuse into the scan body
            self._pend_slots.append(slot)
            self._pend_weights.append(float(self.weights[c]))
            self._pend_payloads.append(payload)
            self._pend_q8.append(packet.wire_dtype != "f32")
            self._pend_scales.append(packet.scale)
            self._pend_clients.append(c)
            self.stats.data_enqueued += 1
            return []
        if self.cfg.ring_assign == "slot":
            worker = slot % self.cfg.n_workers
        else:
            worker = self._rr_next
            self._rr_next = (self._rr_next + 1) % self.cfg.n_workers
        if packet.wire_dtype != "f32":
            # eager path: wire-decode at RX (same elementwise q * scale
            # the fused q8 kernel applies, so numerics are unchanged)
            row = (np.asarray(payload, np.int8).astype(np.float32)
                   * np.float32(packet.scale))
        else:
            row = np.asarray(payload, np.float32)
        if self._tab is not None:         # robust table modes (§11)
            self._tab[c, slot] = row
            self._tab_mask[c, slot] = 1.0
        ring = self._rings[worker]
        ring.append((slot, float(self.weights[c]), row))
        self.stats.data_enqueued += 1
        if len(ring) >= self.cfg.ring_capacity:
            self._drain(worker)
        return []

    # -- worker cores ---------------------------------------------------------
    def _drain(self, worker: int) -> None:
        ring = self._rings[worker]
        if not ring:
            return
        self._rings[worker] = []
        idx = jnp.asarray(np.array([s for s, _, _ in ring], np.int32))
        w = jnp.asarray(np.array([wt for _, wt, _ in ring], np.float32))
        payloads = jnp.asarray(np.stack([p for _, _, p in ring]))
        if self.cfg.agg_mode == "norm_clip":
            # per-packet influence bound: eff_w = w * tau/max(tau, ||row||)
            # (elementwise per packet, so grouping-independent — §11)
            from repro.kernels.packet_scatter import norm_clip_weights
            w = norm_clip_weights(w, payloads, tau=self.cfg.clip_tau)
        self.agg.scatter_add(payloads, idx, weights=w, mode=self.cfg.mode)
        self.stats.batches_drained += 1

    def flush(self) -> None:
        """Drain every ring (the workers' post-END cleanup pass)."""
        for wkr in range(self.cfg.n_workers):
            self._drain(wkr)

    # -- deadline / quorum ----------------------------------------------------
    def _fire_deadline(self) -> None:
        newly = self.fsm.deadline_expired()
        self.stats.stragglers_timed_out += len(newly)
        self._deadline_fired = True

    def _close_round(self) -> None:
        """Close the uplink barrier before the END divide.

        With ``round_deadline`` set, the close *is* the deadline — a
        short stream (fewer events than the budget) still times out its
        stragglers here, so a round's straggler accounting does not
        depend on how much late traffic happened to trail it.  Then the
        quorum guard: a round with fewer clients past their uplink than
        ``min_clients`` raises instead of publishing a global built from
        too few contributions.
        """
        if self.cfg.round_deadline is not None and not self._deadline_fired:
            self._fire_deadline()
        check_quorum(self.fsm.participants(), self.cfg.min_clients,
                     self.stats.stragglers_timed_out)

    # -- END: count-normalized divide ----------------------------------------
    def finalize_round(self, prev_global: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(prev_global (P,)) -> (new_global (P,), counts (N,)).

        Slots with count 0 (nobody delivered the packet) keep the
        previous round's global value — the same count-fallback
        ``fused_round_step`` applies.  With ``cfg.compile`` the recorded
        arrivals are demuxed into a dense drain schedule and the whole
        round — every drain batch, the divide, the fallback — runs as
        one compiled ``lax.scan`` call (DESIGN.md §3).
        """
        self._close_round()
        if self.cfg.compile:
            new_global, counts, _ = self._finalize_compiled(prev_global)
            return new_global, counts
        self.flush()
        if self._tab is not None:
            # robust table modes: per-slot (K, W) client table, fused
            # trimmed-mean/median finalize (DESIGN.md §11).  Client
            # weights are ignored — rank statistics are unweighted.
            from repro.kernels.packet_scatter import robust_finalize_jnp
            table = jnp.asarray(self._tab.swapaxes(0, 1))   # (N, K, W)
            pres = jnp.asarray(self._tab_mask.T)            # (N, K)
            self._tab[...] = 0.0
            self._tab_mask[...] = 0.0
            agg, m = robust_finalize_jnp(
                table, pres, median=(self.cfg.agg_mode == "median"),
                beta=self.cfg.trim_beta)
            agg_flat = depacketize(agg, self.cfg.n_params)
            have = expand_packet_mask(m > 0, self.cfg.payload,
                                      self.cfg.n_params)
            return jnp.where(have, agg_flat, prev_global), m
        avg = self.agg.finalize()                        # (N, W)
        agg_flat = depacketize(avg, self.cfg.n_params)   # (P,)
        have = expand_packet_mask(self.agg.counts > 0, self.cfg.payload,
                                  self.cfg.n_params)
        new_global = jnp.where(have, agg_flat, prev_global)
        return new_global, self.agg.counts

    def finalize_and_distribute(self, prev_global: jnp.ndarray,
                                client_flats: jnp.ndarray,
                                down_mask: jnp.ndarray,
                                mix_alpha: float = 0.0
                                ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                           jnp.ndarray]:
        """END + TX in one step -> (new_global, counts, new_client_flats).

        Under ``cfg.compile`` the downlink fallback is *fused into the
        same compiled call* as the drain scan and the divide — one
        device dispatch for the whole round.
        """
        if self.cfg.compile:
            self._close_round()
            return self._finalize_compiled(prev_global, client_flats,
                                           down_mask, mix_alpha)
        new_global, counts = self.finalize_round(prev_global)  # closes there
        new_flats = self.distribute(new_global, client_flats, down_mask,
                                    mix_alpha=mix_alpha)
        return new_global, counts, new_flats

    def _finalize_compiled(self, prev_global, client_flats=None,
                           down_mask=None, mix_alpha: float = 0.0):
        from repro.core import engine_compiled as ec
        n_q8 = sum(self._pend_q8)
        scales = None
        if n_q8 == 0:
            pay = (np.asarray(self._pend_payloads, np.float32)
                   if self._pend_payloads
                   else np.zeros((0, self.cfg.payload), np.float32))
        elif n_q8 == len(self._pend_payloads):
            # homogeneous q8 round: int8 schedule + scale column, the
            # dequantize runs fused inside the compiled scan
            pay = np.asarray(self._pend_payloads, np.int8)
            scales = np.asarray(self._pend_scales, np.float32)
        else:
            # mixed wire round: decode q8 rows host-side (coexistence
            # fallback, numerics unchanged — DESIGN.md §9)
            pay = np.stack([
                np.asarray(p, np.int8).astype(np.float32) * np.float32(s)
                if q else np.asarray(p, np.float32)
                for p, q, s in zip(self._pend_payloads, self._pend_q8,
                                   self._pend_scales)])
        sched = ec.build_drain_schedule(
            np.asarray(self._pend_slots, np.int32),
            np.asarray(self._pend_weights, np.float32),
            pay,
            n_workers=self.cfg.n_workers,
            ring_capacity=self.cfg.ring_capacity,
            ring_assign=self.cfg.ring_assign, scales=scales,
            clients=np.asarray(self._pend_clients, np.int32))
        self._pend_slots, self._pend_weights, self._pend_payloads = [], [], []
        self._pend_q8, self._pend_scales, self._pend_clients = [], [], []
        total, counts, new_global, new_flats = ec.dispatch_round(
            self.cfg, sched, self.agg.total, self.agg.counts, prev_global,
            client_flats=client_flats, down_mask=down_mask,
            mix_alpha=mix_alpha)
        self.agg.total, self.agg.counts = total, counts
        self.stats.batches_drained += sched.n_batches
        return new_global, counts, new_flats

    # -- TX core: downlink with client fallback ------------------------------
    def distribute(self, new_global: jnp.ndarray, client_flats: jnp.ndarray,
                   down_mask: jnp.ndarray,
                   mix_alpha: float = 0.0) -> jnp.ndarray:
        """new_global (P,); client_flats (K, P); down_mask (K, N) ->
        (K, P) client state after the downlink (lost elements stay
        local; optional APFL-style blend)."""
        down_elem = expand_packet_mask(down_mask, self.cfg.payload,
                                       self.cfg.n_params)
        new_flats = jnp.where(down_elem > 0, new_global[None, :],
                              client_flats)
        if mix_alpha > 0:
            new_flats = mix_alpha * client_flats + (1 - mix_alpha) * new_flats
        return new_flats

    def up_mask(self) -> jnp.ndarray:
        """(K, N) deduplicated protocol-level arrival mask.

        One pass over the FSM's uplink sets builds the (client, slot)
        index arrays and a single fancy-index assignment sets the mask —
        the old per-(client, slot) double loop cost O(K·N) interpreter
        work once per round in every benchmark row.
        """
        m = np.zeros((self.cfg.n_clients, self.cfg.n_slots), np.float32)
        pairs = [(c, s) for c, got in enumerate(self.fsm.uplink)
                 for s in got]
        if pairs:
            cs, ss = np.asarray(pairs, np.int64).T
            m[cs, ss] = 1.0
        return jnp.asarray(m)


# ---------------------------------------------------------------------------
# Async buffered mode (FedBuff) — eager twin (DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AsyncStats:
    """Accounting for one async demux call (conservation invariants:
    ``data_enqueued + duplicates_dropped + phase_dropped`` equals the
    wire DATA count, and ``data_enqueued - data_in_flight`` equals the
    packets actually folded)."""
    data_enqueued: int = 0        # unique DATA accepted into open sessions
    duplicates_dropped: int = 0   # same (client, session, slot) again
    phase_dropped: int = 0        # DATA outside an open session
    malformed_dropped: int = 0    # non-finite payload / bad q8 scale
    control_replies: int = 0      # START_ACK / END_ACK emitted
    batches_drained: int = 0      # scatter-accumulate rows folded
    updates_accepted: int = 0     # ENDs that folded a session's update
    emits: int = 0                # globals published (every B updates)
    data_in_flight: int = 0       # accepted DATA in sessions still open
    updates_in_flight: int = 0    # sessions still open at stream end
    staleness_hist: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class UpdateRecord:
    """One folded client update — the async audit-log row.  Weights are
    reproducible from this log: the update's packets folded with
    ``staleness_weights(base_w, staleness, ...)``."""
    client: int
    session: int          # per-client session ordinal (0-based)
    version_sent: int     # global version stamped on the session's START
    fold_version: int     # server version when the update folded
    staleness: int        # max(0, fold_version - version_sent)
    n_packets: int        # deduplicated DATA rows folded
    window: int           # fold window ordinal within the call


@dataclasses.dataclass
class AsyncState:
    """Carried accumulator between async demux calls: the residual
    (< buffer_size) updates stay folded in ``total``/``counts`` and the
    next call's first emit completes the buffer."""
    total: jnp.ndarray    # (N, W) residual accumulator
    counts: jnp.ndarray   # (N,) residual weighted counts
    global_: jnp.ndarray  # (P,) latest published global
    version: int          # emits so far (the wire version tag source)
    pending: int          # updates folded since the last emit (< B)

    @classmethod
    def init(cls, cfg: EngineConfig,
             prev_global: jnp.ndarray) -> "AsyncState":
        return cls(total=jnp.zeros((cfg.n_slots, cfg.payload), jnp.float32),
                   counts=jnp.zeros((cfg.n_slots,), jnp.float32),
                   global_=jnp.asarray(prev_global, jnp.float32),
                   version=0, pending=0)


@dataclasses.dataclass
class AsyncResult:
    globals_: jnp.ndarray      # (E, P) emitted globals, in emit order
    emit_counts: jnp.ndarray   # (E, N) per-slot weighted counts per emit
    state: AsyncState          # carried accumulator / version / pending
    stats: AsyncStats
    updates: List[UpdateRecord]


class AsyncServerEngine:
    """Eager async buffered server (DESIGN.md §10) — the oracle twin of
    ``engine_compiled.run_compiled_async``.

    No round barrier: each client runs its own upload *session*
    (START ... DATA ... END, the START stamped with the global version
    the client trained on), sessions interleave freely, and the server
    folds a session's deduplicated packets at its END.  Every
    ``cfg.buffer_size`` folded updates the engine *emits*: the
    count-normalized divide with per-slot fallback to the latest global
    (the synchronous END dataflow, verbatim), then the accumulator
    resets and the version increments.  Staleness weighting
    (``kernels.packet_scatter.staleness_weights``) scales each update's
    packet weights by its age at fold time.

    Operationally the fold is batched per emit window — every window's
    packets stream through the same ring demux as a synchronous round
    (rr pointer and rings reset at each emit) — so the compiled
    schedule replays the eager batching exactly, which is what makes
    the differential harness bitwise (DESIGN.md §10).
    """

    def __init__(self, cfg: EngineConfig, prev_global: jnp.ndarray,
                 weights: Optional[jnp.ndarray] = None,
                 state: Optional[AsyncState] = None):
        if cfg.buffer_size is None:
            raise ValueError("AsyncServerEngine needs cfg.buffer_size")
        self.cfg = cfg
        self.weights = (np.ones(cfg.n_clients, np.float32) if weights is None
                        else np.asarray(weights, np.float32))
        if state is None:
            state = AsyncState.init(cfg, prev_global)
        self.agg = StreamingAggregator(cfg.n_slots, cfg.payload,
                                       use_kernel=cfg.use_kernel)
        # copy the carried accumulators: the drain path donates its
        # buffers, and the caller's AsyncState must stay readable
        self.agg.total = jnp.array(state.total, jnp.float32, copy=True)
        self.agg.counts = jnp.array(state.counts, jnp.float32, copy=True)
        self.global_ = jnp.asarray(state.global_, jnp.float32)
        self.version = int(state.version)
        self.pending = int(state.pending)
        K = cfg.n_clients
        self._up = [False] * K                 # session open?
        self._sess = [-1] * K                  # session ordinal
        self._ver = [0] * K                    # version-at-send
        self._seen: List[set] = [set() for _ in range(K)]
        self._buf: List[list] = [[] for _ in range(K)]
        # current window: (slot, base_w, staleness, payload, q8, scale)
        self._win: List[tuple] = []
        self.globals_: List[jnp.ndarray] = []
        self.emit_counts: List[jnp.ndarray] = []
        self.updates: List[UpdateRecord] = []
        self.stats = AsyncStats()

    # -- RX: session grammar --------------------------------------------------
    def rx(self, packet: Packet, payload=None) -> List[Packet]:
        c = packet.client
        if packet.kind == Kind.START:
            self.stats.control_replies += 1
            if not self._up[c]:
                self._up[c] = True
                self._sess[c] += 1
                self._ver[c] = int(packet.version)
                self._seen[c] = set()
                self._buf[c] = []
            # duplicate START mid-session: re-acked, no session reset
            return [Packet(Kind.START_ACK, c)]
        if packet.kind == Kind.END:
            self.stats.control_replies += 1
            if self._up[c]:
                self._fold_update(c)
                self._up[c] = False
            # END outside a session (dup / late): grace re-ack
            return [Packet(Kind.END_ACK, c)]
        if packet.kind != Kind.DATA:
            return []
        if payload_malformed(payload, packet.wire_dtype != "f32",
                             packet.scale):
            self.stats.malformed_dropped += 1
            return []
        if not self._up[c]:
            self.stats.phase_dropped += 1
            return []
        slot = packet.index
        if slot in self._seen[c]:
            self.stats.duplicates_dropped += 1
            return []
        assert payload is not None, "DATA packet without payload"
        self._seen[c].add(slot)
        self._buf[c].append((slot, payload, packet.wire_dtype != "f32",
                             packet.scale))
        self.stats.data_enqueued += 1
        return []

    def _fold_update(self, c: int) -> None:
        staleness = max(0, self.version - self._ver[c])
        window = self.stats.emits
        self.updates.append(UpdateRecord(
            c, self._sess[c], self._ver[c], self.version, staleness,
            len(self._buf[c]), window))
        self.stats.updates_accepted += 1
        h = self.stats.staleness_hist
        h[staleness] = h.get(staleness, 0) + 1
        base_w = float(self.weights[c])
        for slot, pay, q8, sc in self._buf[c]:
            self._win.append((slot, base_w, staleness, pay, q8, sc))
        self._buf[c] = []
        self.pending += 1
        if self.pending >= self.cfg.buffer_size:
            self._emit()

    # -- fold: one emit window through the ring demux -------------------------
    def _fold_window(self) -> None:
        if not self._win:
            return
        from repro.kernels.packet_scatter import staleness_weights
        slots = np.asarray([e[0] for e in self._win], np.int64)
        base_w = np.asarray([e[1] for e in self._win], np.float32)
        stal = np.asarray([e[2] for e in self._win], np.float32)
        q8 = [e[4] for e in self._win]
        n_q8 = sum(q8)
        # same tri-state as the compiled demux (DESIGN.md §9): the norm
        # weighting must see exactly the rows the accumulator sees
        if n_q8 == 0:
            rows = np.asarray([e[3] for e in self._win], np.float32)
            h_rows, h_scales = rows, None
        elif n_q8 == len(self._win):
            h_rows = np.asarray([e[3] for e in self._win], np.int8)
            h_scales = np.asarray([e[5] for e in self._win], np.float32)
            rows = h_rows.astype(np.float32) * h_scales[:, None]
        else:
            rows = np.stack([
                np.asarray(p, np.int8).astype(np.float32) * np.float32(s)
                if q else np.asarray(p, np.float32)
                for _, _, _, p, q, s in self._win])
            h_rows, h_scales = rows, None
        eff = np.asarray(staleness_weights(
            jnp.asarray(base_w), jnp.asarray(stal),
            rows=jnp.asarray(h_rows),
            scales=None if h_scales is None else jnp.asarray(h_scales),
            mode=self.cfg.staleness_mode, alpha=self.cfg.staleness_alpha,
            norm_clip=self.cfg.norm_clip))
        if self.cfg.agg_mode == "norm_clip":
            # composes *after* staleness weighting, in both engines (§11)
            from repro.kernels.packet_scatter import norm_clip_weights
            eff = np.asarray(norm_clip_weights(
                jnp.asarray(eff), jnp.asarray(h_rows),
                scales=None if h_scales is None else jnp.asarray(h_scales),
                tau=self.cfg.clip_tau))
        # fresh ring demux per window: rings and the rr pointer reset at
        # every emit, so each window batches exactly like one sync round
        rings: List[list] = [[] for _ in range(self.cfg.n_workers)]
        rr = 0
        for i in range(len(self._win)):
            if self.cfg.ring_assign == "slot":
                worker = int(slots[i]) % self.cfg.n_workers
            else:
                worker = rr
                rr = (rr + 1) % self.cfg.n_workers
            ring = rings[worker]
            ring.append(i)
            if len(ring) >= self.cfg.ring_capacity:
                self._drain_rows(ring, slots, eff, rows)
                rings[worker] = []
        for worker in range(self.cfg.n_workers):
            self._drain_rows(rings[worker], slots, eff, rows)
        self._win = []

    def _drain_rows(self, members: List[int], slots, eff, rows) -> None:
        if not members:
            return
        m = np.asarray(members, np.int64)
        self.agg.scatter_add(jnp.asarray(rows[m]),
                             jnp.asarray(slots[m].astype(np.int32)),
                             weights=jnp.asarray(eff[m]),
                             mode=self.cfg.mode)
        self.stats.batches_drained += 1

    # -- emit: divide + fallback + reset + version++ --------------------------
    def _emit(self) -> None:
        self._fold_window()
        counts = self.agg.counts
        avg = self.agg.finalize()                        # (N, W)
        agg_flat = depacketize(avg, self.cfg.n_params)   # (P,)
        have = expand_packet_mask(counts > 0, self.cfg.payload,
                                  self.cfg.n_params)
        g = jnp.where(have, agg_flat, self.global_)
        self.globals_.append(g)
        self.emit_counts.append(counts)
        self.global_ = g
        self.agg.reset()
        self.version += 1
        self.pending = 0
        self.stats.emits += 1

    # -- stream end -----------------------------------------------------------
    def finish(self) -> AsyncResult:
        """Fold the residual (< B) updates into the carried accumulator
        — no emit — and account the sessions still open (in-flight:
        buffered this call, not folded, not carried)."""
        self._fold_window()
        for c in range(self.cfg.n_clients):
            if self._up[c]:
                self.stats.updates_in_flight += 1
                self.stats.data_in_flight += len(self._buf[c])
        P = self.cfg.n_params
        E = len(self.globals_)
        globals_ = (jnp.stack(self.globals_) if E
                    else jnp.zeros((0, P), jnp.float32))
        emit_counts = (jnp.stack(self.emit_counts) if E
                       else jnp.zeros((0, self.cfg.n_slots), jnp.float32))
        state = AsyncState(self.agg.total, self.agg.counts, self.global_,
                           self.version, self.pending)
        return AsyncResult(globals_, emit_counts, state, self.stats,
                           list(self.updates))


def run_async_engine(cfg: EngineConfig, events: Iterable,
                     prev_global: jnp.ndarray,
                     weights: Optional[jnp.ndarray] = None,
                     state: Optional[AsyncState] = None) -> AsyncResult:
    """Drive one async demux call over an event stream (DESIGN.md §10).

    With ``cfg.compile`` the stream routes through the compiled bulk
    path (``engine_compiled.run_compiled_async``): one host demux pass
    builds the stacked per-window drain schedule and the whole call —
    every window's fold, every emit's divide — runs as one jitted
    ``lax.scan``.  Outputs are bitwise identical to this eager engine
    for exactly-representable payload sums (the differential harness,
    tests/test_engine_async.py).
    """
    if cfg.buffer_size is None:
        raise ValueError("async engine needs cfg.buffer_size")
    if cfg.compile:
        from repro.core.engine_compiled import run_compiled_async
        return run_compiled_async(cfg, events, prev_global,
                                  weights=weights, state=state)
    engine = AsyncServerEngine(cfg, prev_global, weights=weights,
                               state=state)
    for packet, payload in events:
        engine.rx(packet, payload)
    return engine.finish()


# ---------------------------------------------------------------------------
# Stream generation: lossy / out-of-order / duplicated uplink traffic
# ---------------------------------------------------------------------------

def make_uplink_stream(rng: np.random.Generator, client_pk: jnp.ndarray,
                       *, loss_rate: float = 0.0, dup_rate: float = 0.0,
                       shuffle: bool = True,
                       scales: Optional[jnp.ndarray] = None,
                       versions: Optional[np.ndarray] = None
                       ) -> Tuple[list, jnp.ndarray]:
    """Build one round's interleaved uplink from packetized client state.

    client_pk (K, N, W).  Each DATA packet is dropped with probability
    ``loss_rate``; each survivor is duplicated with probability
    ``dup_rate``; delivery order is shuffled across clients and packets
    (UDP reordering).  START frames precede all data, END frames follow
    (the FSM only accepts DATA between them).

    With ``scales`` (K, N) the stream is the compressed uplink
    (DESIGN.md §9): client_pk then carries the int8 wire payloads (from
    ``packets.packetize_q8`` / ``QuantClientState.encode``) and each
    DATA packet is stamped ``wire_dtype='q8'`` with its per-packet
    dequant scale in the header.  Loss/dup/reorder draws consume the
    identical rng sequence either way, so an f32 and a q8 stream built
    from the same generator state see the same wire fate per packet.

    Returns (events, up_mask): events is a list of ``(Packet, payload)``
    pairs consumable by :meth:`ServerEngine.rx`; up_mask (K, N) marks
    packets that arrived at least once — by construction also the
    engine's post-dedup arrival mask.

    The loss/duplication draws and the delivery order are vectorized
    numpy (two Bernoulli matrices + one permutation), so generating a
    large-K stream is event-list construction, not RNG calls in a
    per-(client, slot) double loop.

    ``versions`` (K,) int stamps every packet of client ``c``'s session
    with the global-version tag ``versions[c]`` (DESIGN.md §10): the
    async server reads version-at-send from the START and measures
    staleness at fold time.  Synchronous rounds leave it at 0.
    """
    K, N, _ = client_pk.shape
    pk_host = np.asarray(client_pk)
    ver = (np.zeros(K, np.int64) if versions is None
           else np.asarray(versions, np.int64))
    keep = (rng.random((K, N)) >= loss_rate if loss_rate > 0.0
            else np.ones((K, N), bool))
    dup_draw = (rng.random((K, N)) < dup_rate if dup_rate > 0.0
                else np.zeros((K, N), bool))
    cs, ns = np.nonzero(keep)
    # duplicates ride adjacent to their original (UDP re-delivery); a
    # single permutation then models cross-client reordering
    reps = 1 + (dup_draw[cs, ns]).astype(np.int64)
    cl, sl = np.repeat(cs, reps), np.repeat(ns, reps)
    if shuffle:
        perm = rng.permutation(cl.size)
        cl, sl = cl[perm], sl[perm]
    events = [(Packet(Kind.START, c, version=int(ver[c])), None)
              for c in range(K)]
    if scales is None:
        events += [(Packet(Kind.DATA, int(c), int(s),
                           version=int(ver[c])), pk_host[c, s])
                   for c, s in zip(cl.tolist(), sl.tolist())]
    else:
        sc_host = np.asarray(scales, np.float32)
        events += [(Packet(Kind.DATA, int(c), int(s), wire_dtype="q8",
                           scale=float(sc_host[c, s]),
                           version=int(ver[c])), pk_host[c, s])
                   for c, s in zip(cl.tolist(), sl.tolist())]
    events += [(Packet(Kind.END, c, version=int(ver[c])), None)
               for c in range(K)]
    return events, jnp.asarray(keep.astype(np.float32))


def run_engine_round(cfg: EngineConfig, client_flats: jnp.ndarray,
                     prev_global: jnp.ndarray, events: Iterable,
                     down_mask: Optional[jnp.ndarray] = None,
                     weights: Optional[jnp.ndarray] = None,
                     mix_alpha: float = 0.0) -> RoundResult:
    """Drive one full round: RX the event stream, divide at END, TX.

    client_flats (K, P) is only used for the downlink fallback; the
    uplink payloads travel inside ``events`` (see make_uplink_stream).
    With integer-valued payloads the exact-mode result is bitwise
    identical to ``aggregation.fused_round_step`` on ``up_mask()`` /
    ``down_mask`` (tests/test_server_engine.py).

    With ``cfg.compile`` the whole round routes through the compiled
    engine's bulk path (core/engine_compiled.py): a vectorized demux
    pass replaces the per-packet RX loop and the round executes as one
    jitted ``lax.scan`` with the END divide and TX downlink fused in —
    bitwise identical outputs, one device dispatch.
    """
    if cfg.compile:
        from repro.core.engine_compiled import run_compiled_round
        return run_compiled_round(cfg, client_flats, prev_global, events,
                                  down_mask=down_mask, weights=weights,
                                  mix_alpha=mix_alpha)
    engine = ServerEngine(cfg, weights=weights)
    for packet, payload in events:
        engine.rx(packet, payload)
    new_global, counts = engine.finalize_round(prev_global)
    new_flats = None
    if down_mask is not None:
        new_flats = engine.distribute(new_global, client_flats, down_mask,
                                      mix_alpha=mix_alpha)
    return RoundResult(new_global, counts, engine.up_mask(), new_flats,
                       engine.stats)


def run_hier_round(cfg: EngineConfig, client_flats, prev_global,
                   events: Iterable, down_mask=None, weights=None,
                   mix_alpha: float = 0.0) -> RoundResult:
    """Eager per-host twin of the hierarchical compiled round
    (DESIGN.md §12): ``cfg.hosts`` independent eager ``ServerEngine``
    leaves, each fed only the packets of the client range it owns, and
    a host-level combine of their raw ``(total, counts)`` accumulators
    — or, for the robust table modes, their client tables — before ONE
    global END divide / rank-select finalize.

    This is the differential reference the hierarchical tests diff the
    compiled engine against (tests/test_engine_hier.py): a real leaf
    host sees only its own clients' packets and runs its own rings, so
    this twin reproduces the per-host *batch composition* exactly —
    which is what makes it the right oracle for approx mode and
    ``norm_clip`` too, where batching changes numerics and the
    unsharded engine does not agree.

    Semantics notes:

    - Each leaf runs with ``min_clients=0``; quorum is a *global*
      property of the round, checked here over the summed participant
      counts (same ``check_quorum`` wording as every other close).
    - ``round_deadline`` / ``buffer_size`` are rejected: a deadline is
      a position in the *global* event stream, which has no meaning in
      a leaf's filtered stream, and the async window grammar is its own
      driver (``run_async_engine``).
    - ``stats`` sums the per-host counters.  ``batches_drained`` is the
      per-host total, which legitimately differs from the unsharded
      engine's count (H partial flushes instead of one); the conserved
      quantities — ``data_enqueued``, drop buckets, replies — are what
      the tests compare.
    """
    from repro.runtime.sharding import HostCtx
    if cfg.round_deadline is not None:
        raise ValueError(
            "run_hier_round: round_deadline positions index the global "
            "event stream and do not map to per-host streams "
            "(DESIGN.md §12)")
    if cfg.buffer_size is not None:
        raise ValueError(
            "run_hier_round is a synchronous-round twin; async buffered "
            "mode has its own driver (run_async_engine)")
    hosts = [HostCtx(h, cfg.hosts, cfg.n_clients)
             for h in range(cfg.hosts)]
    leaf_cfg = dataclasses.replace(cfg, hosts=1, shards=1, compile=False,
                                   min_clients=0, round_deadline=None)
    engines = [ServerEngine(leaf_cfg, weights=weights) for _ in hosts]
    for packet, payload in events:
        for ctx, eng in zip(hosts, engines):
            if ctx.owns(packet.client):
                eng.rx(packet, payload)
                break
    replies = sum(e.stats.control_replies for e in engines)
    for eng in engines:
        eng._close_round()
        eng.flush()
    check_quorum(sum(e.fsm.participants() for e in engines),
                 cfg.min_clients,
                 sum(e.stats.stragglers_timed_out for e in engines))
    robust_table = cfg.agg_mode in ("trimmed_mean", "median")
    if robust_table:
        # host-level combine of the client tables: each (client, slot)
        # row lives on exactly one host, so the sum is a disjoint merge
        tab = np.zeros((cfg.n_clients, cfg.n_slots, cfg.payload),
                       np.float32)
        mask = np.zeros((cfg.n_clients, cfg.n_slots), np.float32)
        for eng in engines:
            tab += eng._tab
            mask += eng._tab_mask
        from repro.kernels.packet_scatter import robust_finalize_jnp
        table = jnp.asarray(tab.swapaxes(0, 1))          # (N, K, W)
        pres = jnp.asarray(mask.T)                       # (N, K)
        agg, counts = robust_finalize_jnp(
            table, pres, median=(cfg.agg_mode == "median"),
            beta=cfg.trim_beta)
    else:
        # host-level combine of the raw accumulators — the outer level
        # of the two-level partial sum, then the one global END divide
        # (the exact op sequence of StreamingAggregator.finalize)
        total = sum(jnp.asarray(e.agg.total) for e in engines)
        counts = sum(jnp.asarray(e.agg.counts) for e in engines)
        agg = total / jnp.maximum(counts, 1e-12)[:, None]
        agg = jnp.where(counts[:, None] > 0, agg, 0.0)
    agg_flat = depacketize(agg, cfg.n_params)
    have = expand_packet_mask(counts > 0, cfg.payload, cfg.n_params)
    new_global = jnp.where(have, agg_flat, jnp.asarray(prev_global))
    up = sum(np.asarray(e.up_mask()) for e in engines)   # disjoint clients
    new_flats = None
    if down_mask is not None:
        down_elem = expand_packet_mask(down_mask, cfg.payload,
                                       cfg.n_params)
        new_flats = jnp.where(down_elem > 0, new_global[None, :],
                              jnp.asarray(client_flats))
        if mix_alpha > 0:
            new_flats = (mix_alpha * jnp.asarray(client_flats)
                         + (1 - mix_alpha) * new_flats)
    stats = EngineStats(
        data_enqueued=sum(e.stats.data_enqueued for e in engines),
        duplicates_dropped=sum(e.stats.duplicates_dropped
                               for e in engines),
        phase_dropped=sum(e.stats.phase_dropped for e in engines),
        batches_drained=sum(e.stats.batches_drained for e in engines),
        control_replies=replies,
        stragglers_timed_out=sum(e.stats.stragglers_timed_out
                                 for e in engines),
        late_dropped=sum(e.stats.late_dropped for e in engines),
        malformed_dropped=sum(e.stats.malformed_dropped for e in engines))
    return RoundResult(new_global, counts, jnp.asarray(up), new_flats,
                       stats)
