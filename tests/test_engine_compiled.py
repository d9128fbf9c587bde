"""Compiled round engine: parity, demux, donation, overlap contracts.

The load-bearing acceptance properties (ISSUE 3 + DESIGN.md §3):

1. ``compile=True`` is **bitwise identical** to the eager ``ServerEngine``
   and (exact mode) to ``aggregation.fused_round_step`` over lossy /
   duplicated / out-of-order streams — both modes, both demux policies,
   ragged final batches.  Approx-mode equality is the strong check: the
   last-writer-wins race is scoped to a drain batch, so it only holds if
   the demux pass reproduces the eager engine's batching *exactly*.
2. The jnp scan body and the Pallas grid kernel implement one contract
   (bitwise on integer payloads, where f32 sums are order-independent).
3. The round dispatch *donates* the (total, counts) accumulators — no
   fresh (N, W) buffer per drain/scan step.
"""
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _drain_reference import (accepted_rows, assert_expands_to,
                              dense_payloads)
from _hypothesis_shim import given, settings, st
from _pool_checks import (held_schedules_keep_their_rows,
                          pool_rounds, pooled_rounds_match_reference)

from repro.core import engine_compiled as ec
from repro.core.aggregation import fused_round_step
from repro.core.packets import packetize
from repro.core.protocol import Kind, Packet
from repro.core.server import (EngineConfig, ServerEngine,
                               make_uplink_stream, run_engine_round)
from repro.kernels.packet_scatter import (packet_scatter_accum_batch_jnp,
                                          packet_scatter_accum_pallas)


def _round_inputs(seed, k=10, p=1000, w=64, int_valued=True):
    rng = np.random.default_rng(seed)
    draw = (rng.integers(-8, 9, (k, p)) if int_valued
            else rng.normal(size=(k, p)))
    flats = jnp.asarray(draw.astype(np.float32))
    prev = jnp.asarray(rng.integers(-8, 9, p).astype(np.float32))
    pk = jax.vmap(lambda f: packetize(f, w))(flats)
    return rng, flats, prev, pk


def _assert_rounds_equal(a, b, flats_too=True):
    np.testing.assert_array_equal(np.asarray(a.new_global),
                                  np.asarray(b.new_global))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.up_mask),
                                  np.asarray(b.up_mask))
    if flats_too and a.new_client_flats is not None:
        np.testing.assert_array_equal(np.asarray(a.new_client_flats),
                                      np.asarray(b.new_client_flats))


@pytest.mark.parametrize("mode", ["exact", "approx"])
@pytest.mark.parametrize("assign", ["rr", "slot"])
@pytest.mark.parametrize("cap", [1, 7, 32])
def test_compiled_bitwise_matches_eager(mode, assign, cap):
    """Both modes, both demux policies, ragged final batches: the
    compiled scan must be bitwise-equal to the eager per-drain engine
    (approx equality proves the drain schedule replays eager batching
    exactly — the race window is the batch)."""
    rng, flats, prev, pk = _round_inputs(42, k=6, p=480, w=48)
    weights = jnp.asarray(rng.integers(1, 4, 6).astype(np.float32))
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.3, dup_rate=0.3)
    down = jnp.asarray((rng.random((6, pk.shape[1])) > 0.2)
                       .astype(np.float32))
    kw = dict(n_clients=6, n_params=480, payload=48, ring_capacity=cap,
              mode=mode, ring_assign=assign)
    eager = run_engine_round(EngineConfig(**kw), flats, prev, events,
                             down_mask=down, weights=weights)
    comp = run_engine_round(EngineConfig(compile=True, **kw), flats, prev,
                            events, down_mask=down, weights=weights)
    _assert_rounds_equal(eager, comp)
    for f in ("data_enqueued", "duplicates_dropped", "phase_dropped",
              "batches_drained", "control_replies"):
        assert getattr(eager.stats, f) == getattr(comp.stats, f), f


def test_compiled_exact_bitwise_matches_fused_round_step():
    """The acceptance criterion: compiled engine == fused_round_step on
    the same masks, bitwise (integer payloads)."""
    rng, flats, prev, pk = _round_inputs(3)
    weights = jnp.asarray(rng.integers(1, 4, 10).astype(np.float32))
    events, up = make_uplink_stream(rng, pk, loss_rate=0.25, dup_rate=0.25)
    down = jnp.asarray((rng.random((10, pk.shape[1])) > 0.2)
                       .astype(np.float32))
    cfg = EngineConfig(n_clients=10, n_params=1000, payload=64,
                       ring_capacity=16, compile=True)
    res = run_engine_round(cfg, flats, prev, events, down_mask=down,
                           weights=weights)
    nf, ng, cnt = fused_round_step(flats, up, down, prev, 64, mode="exact",
                                   weights=weights)
    np.testing.assert_array_equal(np.asarray(res.up_mask), np.asarray(up))
    np.testing.assert_array_equal(np.asarray(res.new_global), np.asarray(ng))
    np.testing.assert_array_equal(np.asarray(res.counts), np.asarray(cnt))
    np.testing.assert_array_equal(np.asarray(res.new_client_flats),
                                  np.asarray(nf))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), loss=st.floats(0.0, 0.6),
       dup=st.floats(0.0, 0.5), cap=st.sampled_from([1, 5, 16]),
       mode=st.sampled_from(["exact", "approx"]))
def test_compiled_matches_eager_any_pattern(seed, loss, dup, cap, mode):
    """Property: for ANY loss/duplication pattern the compiled round is
    bitwise the eager round."""
    rng, flats, prev, pk = _round_inputs(seed, k=4, p=320, w=32)
    events, _ = make_uplink_stream(rng, pk, loss_rate=loss, dup_rate=dup)
    kw = dict(n_clients=4, n_params=320, payload=32, ring_capacity=cap,
              mode=mode)
    eager = run_engine_round(EngineConfig(**kw), flats, prev, events)
    comp = run_engine_round(EngineConfig(compile=True, **kw), flats, prev,
                            events)
    _assert_rounds_equal(eager, comp)


@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_per_packet_compile_api_matches_bulk_demux(mode):
    """ServerEngine(compile=True) keeps the per-packet rx API; its
    recorded round must equal both the bulk-demux path and eager."""
    rng, flats, prev, pk = _round_inputs(23, k=5, p=300, w=30)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.2)
    down = jnp.asarray((rng.random((5, pk.shape[1])) > 0.2)
                       .astype(np.float32))
    kw = dict(n_clients=5, n_params=300, payload=30, ring_capacity=8,
              mode=mode)
    eager = run_engine_round(EngineConfig(**kw), flats, prev, events,
                             down_mask=down)
    engine = ServerEngine(EngineConfig(compile=True, **kw))
    for packet, payload in events:
        engine.rx(packet, payload)
    ng, cnt, nf = engine.finalize_and_distribute(prev, flats, down)
    np.testing.assert_array_equal(np.asarray(eager.new_global),
                                  np.asarray(ng))
    np.testing.assert_array_equal(np.asarray(eager.counts), np.asarray(cnt))
    np.testing.assert_array_equal(np.asarray(eager.new_client_flats),
                                  np.asarray(nf))
    assert engine.stats.batches_drained == eager.stats.batches_drained
    # the post-scan accumulator state lands back in the aggregator
    np.testing.assert_array_equal(np.asarray(engine.agg.counts),
                                  np.asarray(cnt))


@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_pallas_scan_body_matches_jnp_twin(mode):
    """The compiled scan's two bodies — Pallas grid kernel (interpret on
    CPU) and the jnp twin — are one contract, bitwise on this data."""
    rng, flats, prev, pk = _round_inputs(5, k=4, p=256, w=32,
                                         int_valued=False)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.2)
    kw = dict(n_clients=4, n_params=256, payload=32, ring_capacity=8,
              mode=mode, compile=True)
    r_pl = run_engine_round(EngineConfig(scan_body="pallas", **kw),
                            flats, prev, events)
    r_np = run_engine_round(EngineConfig(scan_body="jnp", **kw),
                            flats, prev, events)
    _assert_rounds_equal(r_pl, r_np)


def test_batch_jnp_twin_matches_kernel_single_batch():
    """Unit-level: one drained batch through the jnp twin vs the Pallas
    kernel — same inert padding, duplicates, zero weights."""
    rng = np.random.default_rng(11)
    pk = jnp.asarray(rng.integers(-8, 9, (128, 32)).astype(np.float32))
    idx = jnp.asarray(
        np.where(rng.random(128) < 0.2, -1,
                 rng.integers(0, 16, 128)).astype(np.int32))
    w = jnp.asarray(rng.choice([0.0, 1.0, 2.0], 128).astype(np.float32))
    acc = jnp.asarray(rng.integers(-4, 5, (16, 32)).astype(np.float32))
    cnt = jnp.asarray(rng.integers(0, 3, (16, 1)).astype(np.float32))
    for exact in (True, False):
        a1, c1 = packet_scatter_accum_pallas(pk, idx, w, acc, cnt,
                                             exact=exact, interpret=True)
        a2, c2 = packet_scatter_accum_batch_jnp(pk, idx, w, acc, cnt,
                                                exact=exact)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_demux_drops_phase_and_duplicate_packets_like_fsm():
    """Bulk demux mirrors the FSM gate: DATA before START / after END is
    phase-dropped, re-deliveries are dedup-dropped — and the engine's
    two counters see the two cases separately."""
    rng = np.random.default_rng(5)
    pk = jax.vmap(lambda f: packetize(f, 16))(
        jnp.asarray(rng.integers(-8, 9, (1, 64)).astype(np.float32)))
    events = [
        (Packet(Kind.DATA, 0, 0), np.asarray(pk[0, 0])),   # pre-START
        (Packet(Kind.START, 0), None),
        (Packet(Kind.DATA, 0, 1), np.asarray(pk[0, 1])),
        (Packet(Kind.DATA, 0, 1), np.asarray(pk[0, 1])),   # duplicate
        (Packet(Kind.END, 0), None),
        (Packet(Kind.DATA, 0, 2), np.asarray(pk[0, 2])),   # post-END
    ]
    cfg = EngineConfig(n_clients=1, n_params=64, payload=16)
    for compile_ in (False, True):
        eng = ServerEngine(EngineConfig(n_clients=1, n_params=64,
                                        payload=16, compile=compile_))
        for packet, payload in events:
            eng.rx(packet, payload)
        assert eng.stats.phase_dropped == 2
        assert eng.stats.duplicates_dropped == 1
        assert eng.stats.data_enqueued == 1
    _, stats, up = ec.demux_events(cfg, events)
    assert stats.phase_dropped == 2
    assert stats.duplicates_dropped == 1
    assert stats.data_enqueued == 1
    np.testing.assert_array_equal(np.asarray(up).sum(), 1.0)


def test_payloadless_out_of_phase_data_is_dropped_not_crashed():
    """The eager rx phase-drops DATA before its payload assert; the
    bulk demux must tolerate the same malformed packet (and a round
    where every DATA packet is phase-dropped)."""
    cfg = EngineConfig(n_clients=1, n_params=64, payload=16, compile=True)
    events = [(Packet(Kind.START, 0), None),
              (Packet(Kind.END, 0), None),
              (Packet(Kind.DATA, 0, 0), None)]       # post-END, no payload
    prev = jnp.asarray(np.arange(64, dtype=np.float32))
    res = run_engine_round(cfg, jnp.zeros((1, 64)), prev, events)
    assert res.stats.phase_dropped == 1
    assert res.stats.data_enqueued == 0
    np.testing.assert_array_equal(np.asarray(res.new_global),
                                  np.asarray(prev))


def test_round_dispatch_donates_accumulators():
    """The satellite contract: (total, counts) are donated into the
    compiled round — the caller's buffers are consumed (reused in
    place), not copied into a fresh (N, W) allocation per round."""
    cfg = EngineConfig(n_clients=2, n_params=128, payload=32, compile=True,
                       ring_capacity=4)
    rng = np.random.default_rng(0)
    pk = jax.vmap(lambda f: packetize(f, 32))(
        jnp.asarray(rng.integers(-8, 9, (2, 128)).astype(np.float32)))
    events, _ = make_uplink_stream(rng, pk)
    sched, _, _ = ec.demux_events(cfg, events)
    total = jnp.zeros((cfg.n_slots, 32), jnp.float32)
    counts = jnp.zeros((cfg.n_slots,), jnp.float32)
    prev = jnp.zeros((128,), jnp.float32)
    ec.dispatch_round(cfg, sched, total, counts, prev)
    assert total.is_deleted() and counts.is_deleted()
    # the donation is declared in the lowered module, not just dropped
    lowered = jax.jit(
        ec._round_device,
        static_argnames=("mode", "payload", "n_params", "use_pallas",
                         "block_slots", "block_pkts", "mix_alpha",
                         "interpret", "axes", "mesh"),
        donate_argnums=(0, 1)).lower(
        total := jnp.zeros((cfg.n_slots, 32), jnp.float32),
        jnp.zeros((cfg.n_slots,), jnp.float32),
        jnp.asarray(sched.idx), jnp.asarray(sched.weights),
        jnp.asarray(sched.payloads), jnp.asarray(sched.src), None, prev,
        None, None,
        mode="exact", payload=32, n_params=128, use_pallas=False,
        block_slots=8, block_pkts=128, mix_alpha=0.0, interpret=True,
        axes=(), mesh=None)
    assert "tf.aliasing_output" in lowered.as_text()


@pytest.mark.parametrize("assign", ["rr", "slot"])
def test_shipped_rows_expand_to_the_dense_schedule(assign):
    """A round ships only its accepted payload rows; expanded by the
    plain numpy reference or on the device (the round's own gather)
    they are bitwise the dense (n_rows, B, W) tensor of the
    eager batching, on a lossy, duplicated, reordered stream."""
    rng, _, _, pk = _round_inputs(3, int_valued=False)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.3)
    cfg = EngineConfig(n_clients=10, n_params=1000, payload=64,
                       n_workers=3, ring_capacity=7, ring_assign=assign,
                       compile=True)
    sched, _, _ = ec.demux_events(cfg, events)
    slots, rows = accepted_rows(events)
    assert rows.dtype == np.float32 and sched.n_packets == len(slots)
    assert_expands_to(sched, dense_payloads(
        slots, rows, n_workers=3, ring_capacity=7, ring_assign=assign), 7)


def test_empty_round_ships_only_a_zero_tail():
    cfg = EngineConfig(n_clients=2, n_params=64, payload=16, n_workers=3,
                       ring_capacity=4, compile=True)
    events = [(Packet(Kind.START, c), None) for c in range(2)] + \
        [(Packet(Kind.END, c), None) for c in range(2)]
    sched, _, _ = ec.demux_events(cfg, events)
    built = ec.build_drain_schedule(
        np.zeros(0, np.int32), np.zeros(0, np.float32),
        np.zeros((0, 16), np.float32), n_workers=3, ring_capacity=4)
    for s in (sched, built):
        assert s.n_packets == 0 and not s.payloads.any()
        assert_expands_to(s, np.zeros((1, 128, 16), np.float32), 4)


def test_full_batches_leave_no_spare_row_and_pad_past_the_end():
    """Every batch full and the row count already a multiple of 8: the
    shipped rows are exactly the arrivals, no zero row exists, and the
    inert cells must point past the end (-1 would wrap onto the last
    arrival's row)."""
    rng = np.random.default_rng(5)
    slots = rng.permutation(64).astype(np.int32)
    rows = rng.normal(size=(64, 24)).astype(np.float32) + 1.0
    sched = ec.build_drain_schedule(slots, np.ones(64, np.float32), rows,
                                    n_workers=2, ring_capacity=4)
    assert sched.n_batches == sched.idx.shape[0] == 16
    assert sched.payloads.shape[0] == 64 == sched.n_packets
    assert (sched.src[sched.idx < 0] == 64).all()
    assert_expands_to(sched, dense_payloads(slots, rows, n_workers=2,
                                            ring_capacity=4), 4)


def test_shipped_shape_follows_rows_not_accepted_count():
    """Two rounds that accept different numbers of packets into the
    same bucketed row count ship arrays of one shape, so the second
    round compiles nothing."""
    cfg = EngineConfig(n_clients=10, n_params=1000, payload=64,
                       n_workers=3, ring_capacity=7, compile=True)
    scheds = []
    for seed in (0, 1):
        rng, _, prev, pk = _round_inputs(seed, int_valued=False)
        events, _ = make_uplink_stream(rng, pk, loss_rate=0.2,
                                       dup_rate=0.3)
        scheds.append(ec.demux_events(cfg, events)[0])
    a, b = scheds
    assert a.n_packets != b.n_packets
    assert a.idx.shape == b.idx.shape
    assert a.payloads.shape == b.payloads.shape == (a.idx.shape[0] * 7, 64)

    def dispatch(sched):
        return ec.dispatch_round(
            cfg, sched, jnp.zeros((cfg.n_slots, 64), jnp.float32),
            jnp.zeros((cfg.n_slots,), jnp.float32), prev)[2]
    dispatch(a).block_until_ready()
    before = ec._round_device._cache_size()
    dispatch(b).block_until_ready()
    assert ec._round_device._cache_size() == before


def test_ops_scatter_accum_donation_is_opt_in():
    """donate=True consumes the accumulator; the default leaves callers
    free to reuse their arrays (test_kernels.py does)."""
    from repro.kernels import ops
    pk = jnp.ones((8, 32))
    idx = jnp.arange(8, dtype=jnp.int32)
    acc, cnt = jnp.zeros((16, 32)), jnp.zeros((16,))
    a1, c1 = ops.packet_scatter_accum(pk, idx, acc, cnt)
    assert not acc.is_deleted()
    a2, c2 = ops.packet_scatter_accum(pk, idx, acc, cnt, donate=True)
    assert acc.is_deleted() and cnt.is_deleted()
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_streaming_aggregator_donates_per_drain():
    """The per-drain hot path really stops reallocating: the pre-drain
    total buffer is consumed by the donated kernel call."""
    from repro.core.pipeline import StreamingAggregator
    agg = StreamingAggregator(8, 32)
    before_total, before_counts = agg.total, agg.counts
    agg.scatter_add(jnp.ones((4, 32)), jnp.asarray([0, 1, 2, 3]))
    assert before_total.is_deleted() and before_counts.is_deleted()
    fb_total, fb_counts = agg.total, agg.counts
    agg.add_batch(jnp.ones((2, 8, 32)), jnp.ones((2, 8)))
    assert fb_total.is_deleted() and fb_counts.is_deleted()
    np.testing.assert_array_equal(np.asarray(agg.counts)[:4], 3.0)


def test_overlapped_rounds_match_sequential_chain():
    """run_compiled_rounds pipelines demux against device execution but
    must produce the same chained-round results, bitwise."""
    rng, flats, prev, pk = _round_inputs(9, k=4, p=320, w=32)
    cfg = EngineConfig(n_clients=4, n_params=320, payload=32,
                       ring_capacity=8, compile=True)
    rounds = []
    for r in range(3):
        f = jnp.asarray(
            np.random.default_rng(100 + r).integers(-8, 9, (4, 320))
            .astype(np.float32))
        ev, _ = make_uplink_stream(rng, jax.vmap(
            lambda x: packetize(x, 32))(f), loss_rate=0.2, dup_rate=0.2)
        dn = jnp.asarray((rng.random((4, pk.shape[1])) > 0.2)
                         .astype(np.float32))
        rounds.append((ev, f, dn))
    overlapped = ec.run_compiled_rounds(cfg, rounds, prev)
    g = prev
    for (ev, f, dn), got in zip(rounds, overlapped):
        want = run_engine_round(cfg, f, g, ev, down_mask=dn)
        _assert_rounds_equal(want, got)
        g = want.new_global
    assert len(overlapped) == 3


def test_empty_round_falls_back_to_prev_global():
    """A round with no accepted DATA: every slot falls back."""
    cfg = EngineConfig(n_clients=2, n_params=64, payload=16, compile=True)
    prev = jnp.asarray(np.arange(64, dtype=np.float32))
    events = [(Packet(Kind.START, c), None) for c in range(2)]
    events += [(Packet(Kind.END, c), None) for c in range(2)]
    res = run_engine_round(cfg, jnp.zeros((2, 64)), prev, events)
    np.testing.assert_array_equal(np.asarray(res.new_global),
                                  np.asarray(prev))
    np.testing.assert_array_equal(np.asarray(res.counts), 0.0)


def test_make_uplink_stream_vectorized_semantics():
    """The vectorized generator keeps the contract: up_mask == packets
    seen at least once; duplicates ride adjacent when shuffle=False;
    loss=0 delivers everything exactly once + dups."""
    rng = np.random.default_rng(0)
    pk = jnp.asarray(rng.integers(-8, 9, (3, 10, 8)).astype(np.float32))
    events, up = make_uplink_stream(rng, pk, loss_rate=0.3, dup_rate=0.4,
                                    shuffle=False)
    data = [(p.client, p.index) for p, _ in events if p.kind == Kind.DATA]
    seen = set(data)
    assert seen == {(c, n) for c in range(3) for n in range(10)
                    if up[c, n] > 0}
    # duplicates adjacent (pre-shuffle ordering): every repeated pair is
    # contiguous
    for i in range(1, len(data)):
        if data[i] in data[:i]:
            assert data[i] == data[i - 1]
    # payload rows ride with the right packet
    for p, pay in events:
        if p.kind == Kind.DATA:
            np.testing.assert_array_equal(np.asarray(pay),
                                          np.asarray(pk[p.client, p.index]))


# ---------------------------------------------------------------------------
# Host buffer pool: the demux's payload buffers recycled across rounds
# ---------------------------------------------------------------------------

def test_pooled_rounds_reuse_buffers_and_match_reference(monkeypatch):
    pooled_rounds_match_reference(monkeypatch, "f32")


def test_held_schedules_keep_their_rows():
    held_schedules_keep_their_rows("f32")


@pytest.mark.parametrize("wire", ["f32", "q8"])
def test_overlapped_pooled_rounds_match_one_at_a_time(wire):
    """``run_compiled_rounds`` demuxes round r+1 while round r is in
    flight, not blocking between them; over rounds that recycle the
    pool's buffers its results are bitwise the same rounds served one
    at a time, so no transfer reads a buffer leased again."""
    cfg, rounds = pool_rounds(5, wire=wire, seed=23)
    before = ec.host_pool_stats()
    overlapped = ec.run_compiled_rounds(
        cfg, [(events, None, None) for events, _ in rounds],
        jnp.zeros(1000, jnp.float32))
    assert ec.host_pool_stats()["reused"] > before["reused"]
    g = jnp.zeros(1000, jnp.float32)
    for (events, _), got in zip(rounds, overlapped):
        want = ec.run_compiled_round(cfg, None, g, events)
        _assert_rounds_equal(want, got)
        assert got.stats == want.stats
        g = want.new_global
    assert len(overlapped) == 5


def test_pool_keeps_a_bounded_number_of_idle_buffers():
    """Eight schedules held at once need eight buffers; once they are
    released, the next lease leaves at most ``POOL_IDLE`` idle ones of
    that width and dtype besides its own."""
    def schedule():
        return ec.build_drain_schedule(
            np.arange(20, dtype=np.int32), np.ones(20, np.float32),
            np.ones((20, 23), np.float32), n_workers=2, ring_capacity=5)
    kept = ec.host_pool_stats()["kept"]
    held = [schedule() for _ in range(8)]
    assert ec.host_pool_stats()["kept"] == kept + 8
    del held
    last = schedule()
    assert ec.host_pool_stats()["kept"] == kept + ec.POOL_IDLE + 1
    assert (last.payloads[:20] == 1).all()
    assert not last.payloads[20:].any()


def test_pool_leases_no_held_buffer_across_threads():
    """Threads demuxing at once (more threads than cores, a short
    switch interval) never lease a buffer another thread holds: each
    held schedule's shipped rows stay its own after every yield."""
    def worker(k, failures):
        slots = np.arange(40, dtype=np.int32)
        rows = np.full((40, 16), k, np.float32)
        for _ in range(300):
            sched = ec.build_drain_schedule(
                slots, np.ones(40, np.float32), rows, n_workers=3,
                ring_capacity=7)
            threading.Event().wait(0)
            if not (sched.payloads[:40] == k).all():
                failures.append(k)

    failures = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k, failures))
                   for k in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert failures == []
