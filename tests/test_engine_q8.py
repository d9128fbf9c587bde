"""Compressed int8 uplink wire path (DESIGN.md §9).

The load-bearing contract: a q8 round — int8 payloads + per-packet
scale column, dequantize fused into the compiled scan body — is
**bitwise identical** to decoding the same wire bytes on the host and
running the f32 engine on them.  Host decode and kernel decode apply
the same elementwise IEEE ops (``q.astype(f32) * scale``) before the
same routing matmul, and the drain batching is wire-format-agnostic,
so the equality is exact, not approximate — on lossy, duplicated,
out-of-order streams, in both server modes, at any shard count.

Around that core sit the wire-format unit contracts: header byte
accounting, quantize/decode roundtrip error, the error-feedback
residual identity, f32/q8 stream coexistence, and FSM/dedup stats
parity between the eager and compiled engines on q8 streams.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _drain_reference import (accepted_rows, assert_expands_to,
                              dense_payloads)
from _pool_checks import (held_schedules_keep_their_rows,
                          pooled_rounds_match_reference)

from repro.core import packets as pktmod
from repro.core.aggregation import quantize_packets
from repro.core import engine_compiled as ec
from repro.core.packets import (PAYLOAD_BYTES, PAYLOAD_F32, PAYLOAD_Q8,
                                QuantClientState, WIRE_PACKET_BYTES,
                                depacketize_q8, packet_wire_bytes,
                                packetize, packetize_q8,
                                payload_wire_bytes, quantize_payload,
                                quantize_with_feedback)
from repro.core.protocol import Kind, Packet
from repro.core.server import (EngineConfig, ServerEngine,
                               make_uplink_stream, run_engine_round)

K, P, W = 8, 320, 32
N = P // W


def _flats(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(K, P)).astype(np.float32))


def _q8_of(flats):
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    return quantize_packets(pk)


def _dequant_host(q, sc):
    """The host-side wire decode: same elementwise ops as the kernel."""
    return (np.asarray(q).astype(np.float32)
            * np.asarray(sc, np.float32)[..., None])


def _twin_streams(q, sc, seed, **kw):
    """One q8 stream and its host-decoded f32 twin, identical wire fate
    (same rng sequence => same loss/dup/permutation draws)."""
    ev_q8, up1 = make_uplink_stream(np.random.default_rng(seed), q,
                                    scales=sc, **kw)
    ev_f32, up2 = make_uplink_stream(np.random.default_rng(seed),
                                     jnp.asarray(_dequant_host(q, sc)),
                                     **kw)
    np.testing.assert_array_equal(np.asarray(up1), np.asarray(up2))
    return ev_q8, ev_f32


# ---------------------------------------------------------------------------
# Wire format units
# ---------------------------------------------------------------------------

def test_q8_header_byte_accounting():
    # 4 B scale comes out of the 1468 B payload budget
    assert PAYLOAD_Q8 == PAYLOAD_BYTES - 4 == 1464
    assert payload_wire_bytes(PAYLOAD_F32, "f32") == PAYLOAD_BYTES
    assert payload_wire_bytes(PAYLOAD_Q8, "q8") == PAYLOAD_BYTES
    # a full-MTU packet is the same 1538 wire bytes in either format
    assert packet_wire_bytes(PAYLOAD_F32, "f32") == WIRE_PACKET_BYTES
    assert packet_wire_bytes(PAYLOAD_Q8, "q8") == WIRE_PACKET_BYTES
    # at the benchmark payload the q8 packet is ~3.8x smaller on the
    # UDP payload and the weights-per-packet capacity is 4x - scale
    assert payload_wire_bytes(64, "f32") == 256
    assert payload_wire_bytes(64, "q8") == 68
    with pytest.raises(ValueError):
        payload_wire_bytes(64, "f16")


def test_packetize_q8_roundtrip_error_bound():
    rng = np.random.default_rng(3)
    flat = jnp.asarray(rng.normal(size=(P,)).astype(np.float32))
    q, sc = packetize_q8(flat, W)
    assert q.dtype == jnp.int8 and q.shape == (N, W)
    assert sc.shape == (N,)
    decoded = depacketize_q8(q, sc, P)
    # symmetric absmax: error per element <= scale/2 (+eps slack)
    bound = np.repeat(np.asarray(sc), W)[:P] * 0.5 * (1 + 1e-5)
    assert np.all(np.abs(np.asarray(decoded - flat)) <= bound)


def test_quantize_payload_matches_aggregation_shortcut():
    """ONE definition of the encoding: the wire path and the (K, N, W)
    aggregation helper must produce identical bytes and scales."""
    flats = _flats(4)
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    q1, s1 = quantize_packets(pk)
    q2, s2 = quantize_payload(pk)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_error_feedback_residual_identity():
    """decode(sent) + new_residual == flat + old_residual: the residual
    is exactly what the wire could not express this round."""
    rng = np.random.default_rng(5)
    flat = jnp.asarray(rng.normal(size=(P,)).astype(np.float32))
    res0 = jnp.asarray(rng.normal(size=(P,)).astype(np.float32)) * 0.01
    q, sc, res1 = quantize_with_feedback(flat, res0, W)
    decoded = depacketize_q8(q, sc, P)
    np.testing.assert_allclose(np.asarray(decoded + res1),
                               np.asarray(flat + res0), rtol=0, atol=1e-6)
    # and the residual is bounded by half a quantization step per element
    bound = np.repeat(np.asarray(sc), W)[:P] * 0.5 * (1 + 1e-5)
    assert np.all(np.abs(np.asarray(res1)) <= bound)


def test_quant_client_state_chains_residual():
    st = QuantClientState.init(P, W)
    assert float(jnp.sum(jnp.abs(st.residual))) == 0.0
    flat = _flats(6)[0]
    q, sc, st1 = st.encode(flat)
    q2, sc2, _ = st1.encode(flat)
    # the carried residual changes the second round's encoding
    assert np.any(np.asarray(q) != np.asarray(q2))
    # state is immutable: the original encodes identically again
    q3, _, _ = st.encode(flat)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q3))


def test_packet_defaults_are_f32_wire():
    """Adding the wire header must not disturb existing construction."""
    p = Packet(Kind.DATA, 3, 7)
    assert p.wire_dtype == "f32" and p.scale == 1.0


# ---------------------------------------------------------------------------
# The acceptance matrix: q8 compiled round == host-dequantized twin,
# bitwise, across modes x shards x ring demux on lossy/dup/ooo streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4, 8])
@pytest.mark.parametrize("ring_assign", ["rr", "slot"])
@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_q8_compiled_round_bitwise_vs_dequant_twin(mode, ring_assign,
                                                   shards):
    flats = _flats(0)
    q, sc = _q8_of(flats)
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W, ring_capacity=8,
                       compile=True, mode=mode, ring_assign=ring_assign,
                       shards=shards)
    ev_q8, ev_f32 = _twin_streams(q, sc, seed=42, loss_rate=0.1,
                                  dup_rate=0.15)
    prev = jnp.zeros((P,))
    down = jnp.ones((K, N), jnp.float32)
    r_q8 = run_engine_round(cfg, flats, prev, ev_q8, down_mask=down)
    r_f32 = run_engine_round(cfg, flats, prev, ev_f32, down_mask=down)
    np.testing.assert_array_equal(np.asarray(r_q8.new_global),
                                  np.asarray(r_f32.new_global))
    np.testing.assert_array_equal(np.asarray(r_q8.counts),
                                  np.asarray(r_f32.counts))
    np.testing.assert_array_equal(np.asarray(r_q8.new_client_flats),
                                  np.asarray(r_f32.new_client_flats))


@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_q8_eager_engine_matches_compiled(mode):
    """The eager per-packet rx (host decode at RX) and the compiled path
    (decode fused in the scan) are the same round, bitwise."""
    flats = _flats(1)
    q, sc = _q8_of(flats)
    outs = []
    for compile_ in (False, True):
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=8, mode=mode, compile=compile_)
        ev, _ = make_uplink_stream(np.random.default_rng(7), q,
                                   loss_rate=0.1, dup_rate=0.1, scales=sc)
        outs.append(run_engine_round(cfg, flats, jnp.zeros((P,)), ev))
    np.testing.assert_array_equal(np.asarray(outs[0].new_global),
                                  np.asarray(outs[1].new_global))
    np.testing.assert_array_equal(np.asarray(outs[0].counts),
                                  np.asarray(outs[1].counts))
    a, b = outs[0].stats, outs[1].stats
    assert (a.data_enqueued, a.duplicates_dropped, a.phase_dropped) == \
        (b.data_enqueued, b.duplicates_dropped, b.phase_dropped)


def test_q8_scan_body_pallas_matches_jnp():
    """The fused-dequant Pallas kernel (interpret mode here) and its jnp
    twin are interchangeable scan bodies, bitwise."""
    flats = _flats(2)
    q, sc = _q8_of(flats)
    outs = []
    for body in ("pallas", "jnp"):
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=8, compile=True, mode="approx",
                           scan_body=body)
        ev, _ = make_uplink_stream(np.random.default_rng(5), q,
                                   loss_rate=0.1, dup_rate=0.2, scales=sc)
        outs.append(run_engine_round(cfg, flats, jnp.zeros((P,)), ev))
    np.testing.assert_array_equal(np.asarray(outs[0].new_global),
                                  np.asarray(outs[1].new_global))


def test_mixed_wire_round_coexists():
    """Half the clients upload f32, half q8, in ONE round on one
    socket: the FSM/dedup path is wire-agnostic and the round equals
    the all-decoded f32 round (mixed rounds decode q8 host-side)."""
    flats = _flats(3)
    q, sc = _q8_of(flats)
    deq = _dequant_host(q, sc)
    pk_f32 = jnp.asarray(deq)
    for compile_ in (False, True):
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=8, compile=compile_)
        rng = np.random.default_rng(11)
        ev_mixed, _ = make_uplink_stream(rng, q, loss_rate=0.1,
                                         dup_rate=0.1, scales=sc)
        # rewrite clients < K/2 to f32 wire, payload = the decoded rows
        ev_mixed = [
            (pkt, pay) if pkt.kind is not Kind.DATA or pkt.client >= K // 2
            else (dataclasses.replace(pkt, wire_dtype="f32", scale=1.0),
                  deq[pkt.client, pkt.index])
            for pkt, pay in ev_mixed]
        ev_f32, _ = make_uplink_stream(np.random.default_rng(11), pk_f32,
                                       loss_rate=0.1, dup_rate=0.1)
        a = run_engine_round(cfg, flats, jnp.zeros((P,)), ev_mixed)
        b = run_engine_round(cfg, flats, jnp.zeros((P,)), ev_f32)
        np.testing.assert_array_equal(np.asarray(a.new_global),
                                      np.asarray(b.new_global))
        np.testing.assert_array_equal(np.asarray(a.counts),
                                      np.asarray(b.counts))


def test_q8_schedule_stays_int8_end_to_end():
    """No f32 copy of a homogeneous q8 uplink materializes host-side:
    the drain schedule's payload tensor is int8 with a scale column."""
    flats = _flats(4)
    q, sc = _q8_of(flats)
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W, ring_capacity=8,
                       compile=True)
    ev, _ = make_uplink_stream(np.random.default_rng(13), q, loss_rate=0.1,
                               dup_rate=0.1, scales=sc)
    sched, stats, up = ec.demux_events(cfg, ev)
    assert sched.payloads.dtype == np.int8
    assert sched.scales is not None
    assert sched.scales.shape == sched.weights.shape
    assert sched.scales.dtype == np.float32
    # scale is attached exactly where a packet landed, 0 elsewhere
    covered = sched.idx >= 0
    assert np.all(sched.scales[covered] > 0)
    assert np.all(sched.scales[~covered] == 0)
    # the compiled engine's recording rx builds the same schedule
    eng = ServerEngine(cfg)
    for pkt, pay in ev:
        eng.rx(pkt, pay)
    assert all(eng._pend_q8)
    # sharding carries the scale column alongside the weights, and each
    # shard's rows stay int8
    sharded = dataclasses.replace(cfg, shards=4)
    lv = ec.leaf_layout(sharded, [sched])
    assert lv.payloads.dtype == np.int8 and lv.scales is not None
    assert lv.scales.shape == lv.weights.shape == (1, 4) + lv.idx.shape[-2:]
    # every live cell keeps its packet's scale, on whichever shard
    assert sorted(lv.scales[lv.idx >= 0].tolist()) == \
        sorted(sched.scales[sched.idx >= 0].tolist())
    # and the f32 path still reports no scales
    ev_f, _ = make_uplink_stream(
        np.random.default_rng(13), jnp.asarray(_dequant_host(q, sc)),
        loss_rate=0.1, dup_rate=0.1)
    sched_f, _, _ = ec.demux_events(cfg, ev_f)
    assert sched_f.payloads.dtype == np.float32
    assert sched_f.scales is None
    assert ec.leaf_layout(sharded, [sched_f]).scales is None


@pytest.mark.parametrize("wire", ["q8", "mixed"])
def test_shipped_wire_rows_expand_to_the_dense_schedule(wire):
    """The q8 round ships its accepted int8 rows, the mixed-wire round
    its host-decoded f32 rows; either way both expansions are bitwise
    the dense tensor of the eager batching, scales riding on the
    (n_rows, B) grid beside the weights."""
    flats = _flats(6)
    q, sc = _q8_of(flats)
    deq = _dequant_host(q, sc)
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W, n_workers=3,
                       ring_capacity=5, compile=True)
    ev, _ = make_uplink_stream(np.random.default_rng(19), q, loss_rate=0.1,
                               dup_rate=0.2, scales=sc)
    if wire == "mixed":
        ev = [(pkt, pay) if pkt.kind is not Kind.DATA or pkt.client % 2
              else (dataclasses.replace(pkt, wire_dtype="f32", scale=1.0),
                    deq[pkt.client, pkt.index])
              for pkt, pay in ev]
    sched, _, _ = ec.demux_events(cfg, ev)
    slots, rows = accepted_rows(ev, decode=(wire == "mixed"))
    assert rows.dtype == (np.int8 if wire == "q8" else np.float32)
    assert (sched.scales is not None) == (wire == "q8")
    assert_expands_to(sched, dense_payloads(slots, rows, n_workers=3,
                                            ring_capacity=5), 5)


def test_q8_deadline_and_dedup_semantics_unchanged():
    """The wire header rides through the FSM untouched: duplicates,
    phase drops and the deadline close behave exactly as on f32."""
    flats = _flats(5)
    q, sc = _q8_of(flats)
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W, ring_capacity=8,
                       compile=True, round_deadline=60)
    ev_q8, ev_f32 = _twin_streams(q, sc, seed=17, loss_rate=0.05,
                                  dup_rate=0.3)
    a = run_engine_round(cfg, flats, jnp.zeros((P,)), ev_q8)
    b = run_engine_round(cfg, flats, jnp.zeros((P,)), ev_f32)
    np.testing.assert_array_equal(np.asarray(a.new_global),
                                  np.asarray(b.new_global))
    sa, sb = a.stats, b.stats
    assert (sa.data_enqueued, sa.duplicates_dropped, sa.late_dropped,
            sa.stragglers_timed_out) == \
        (sb.data_enqueued, sb.duplicates_dropped, sb.late_dropped,
         sb.stragglers_timed_out)
    assert sa.duplicates_dropped > 0 and sa.late_dropped > 0


def test_q8_pooled_rounds_reuse_buffers_and_match_reference(monkeypatch):
    """The q8 twin of the f32 pool test: a q8 round leases only its
    shipped int8 rows, and reuses them from the third round on."""
    pooled_rounds_match_reference(monkeypatch, "q8")


def test_q8_held_schedules_keep_their_rows():
    held_schedules_keep_their_rows("q8")
