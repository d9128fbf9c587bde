"""Poisoned-packet hardening (DESIGN.md §11): wire-boundary rejection.

A NaN/Inf f32 payload — or a q8 packet whose dequant scale is zero,
negative, or non-finite — must never reach an accumulator: one NaN
survives every subsequent sum.  The engines drop such packets at the
wire boundary, count them in ``malformed_dropped``, and otherwise
behave *exactly* as if the packet were a wire loss:

- eager == compiled on the counter and on every output, all modes;
- a malformed stream is bitwise the clean stream with those events
  deleted (the rr pointer does not advance on a malformed drop);
- the dedup set is not poisoned — a clean retransmission of the same
  (client, slot) is still accepted;
- the conservation identity grows the new bucket:
  ``data_enqueued + duplicates + phase + late + malformed == DATA``;
- async engines drop malformed before the session-phase check, both
  paths agreeing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import quantize_packets
from repro.core.packets import packetize
from repro.core.protocol import Kind
from repro.core.server import (EngineConfig, ServerEngine,
                               make_uplink_stream, payload_malformed,
                               run_async_engine, run_engine_round)

K, P, W = 6, 480, 48
N = P // W


def _round_inputs(seed):
    rng = np.random.default_rng(seed)
    flats = jnp.asarray(rng.integers(-8, 9, (K, P)).astype(np.float32))
    prev = jnp.asarray(rng.integers(-8, 9, P).astype(np.float32))
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    return rng, flats, prev, pk


def _cfg(**kw):
    base = dict(n_clients=K, n_params=P, payload=W, ring_capacity=7)
    base.update(kw)
    return EngineConfig(**base)


def _poison_f32(events, victims, value=np.nan):
    """Corrupt one lane of every copy of the chosen (client, slot) DATA
    payloads; return (poisoned_events, clean_events_without_them, n)."""
    poisoned, clean, n = [], [], 0
    for packet, payload in events:
        if (packet.kind is Kind.DATA
                and (packet.client, packet.index) in victims):
            bad = np.asarray(payload).copy()
            bad[n % W] = value
            poisoned.append((packet, jnp.asarray(bad)))
            n += 1
        else:
            poisoned.append((packet, payload))
            clean.append((packet, payload))
    assert n > 0
    return poisoned, clean, n


def _poison_q8_scale(events, victims, scale):
    poisoned, clean, n = [], [], 0
    for packet, payload in events:
        if (packet.kind is Kind.DATA
                and (packet.client, packet.index) in victims):
            poisoned.append((dataclasses.replace(packet, scale=scale),
                             payload))
            n += 1
        else:
            poisoned.append((packet, payload))
            clean.append((packet, payload))
    assert n > 0
    return poisoned, clean, n


def _assert_rounds_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.new_global),
                                  np.asarray(b.new_global))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.up_mask),
                                  np.asarray(b.up_mask))


# ---------------------------------------------------------------------------
# predicate unit tests
# ---------------------------------------------------------------------------

def test_payload_malformed_predicate():
    ok = np.ones(W, np.float32)
    bad = ok.copy()
    bad[3] = np.inf
    assert not payload_malformed(ok, False, 1.0)
    assert payload_malformed(bad, False, 1.0)
    bad[3] = np.nan
    assert payload_malformed(bad, False, 1.0)
    # f32 scale is ignored; a phase-dropped DATA may carry no payload
    assert not payload_malformed(ok, False, 0.0)
    assert not payload_malformed(None, False, 1.0)
    # q8: the *scale* is the hazard, the int8 payload can't be non-finite
    q = np.ones(W, np.int8)
    assert not payload_malformed(q, True, 0.5)
    for s in (0.0, -1.0, np.nan, np.inf, -np.inf):
        assert payload_malformed(q, True, s)


# ---------------------------------------------------------------------------
# malformed stream == clean-drop twin, eager == compiled
# ---------------------------------------------------------------------------

VICTIMS = {(0, 0), (2, 3), (4, 7)}


@pytest.mark.parametrize("agg", ["mean", "trimmed_mean", "norm_clip"])
@pytest.mark.parametrize("assign", ["rr", "slot"])
@pytest.mark.parametrize("value", [np.nan, np.inf])
def test_f32_malformed_equals_clean_drop_twin(agg, assign, value):
    """The strong check: dropping at the boundary leaves the round
    bitwise identical to the stream where the packets never existed —
    in particular the rr worker pointer must not advance on the drop."""
    rng, flats, prev, pk = _round_inputs(42)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.3)
    poisoned, clean, n_bad = _poison_f32(events, VICTIMS, value)
    for compile_ in (False, True):
        cfg = _cfg(agg_mode=agg, ring_assign=assign, compile=compile_)
        got = run_engine_round(cfg, flats, prev, poisoned)
        want = run_engine_round(cfg, flats, prev, clean)
        _assert_rounds_equal(want, got)
        assert got.stats.malformed_dropped == n_bad
        assert want.stats.malformed_dropped == 0
        assert got.stats.data_enqueued == want.stats.data_enqueued
        assert np.isfinite(np.asarray(got.new_global)).all()


@pytest.mark.parametrize("scale", [0.0, -2.0, np.nan, np.inf])
def test_q8_bad_scale_equals_clean_drop_twin(scale):
    rng, flats, prev, pk = _round_inputs(7)
    q8, sc = quantize_packets(pk)
    events, _ = make_uplink_stream(rng, q8, loss_rate=0.15, dup_rate=0.2,
                                   scales=sc)
    poisoned, clean, n_bad = _poison_q8_scale(events, VICTIMS, scale)
    for compile_ in (False, True):
        cfg = _cfg(compile=compile_)
        got = run_engine_round(cfg, flats, prev, poisoned)
        want = run_engine_round(cfg, flats, prev, clean)
        _assert_rounds_equal(want, got)
        assert got.stats.malformed_dropped == n_bad
        assert np.isfinite(np.asarray(got.new_global)).all()


def test_eager_compiled_counter_parity_mixed_corruption():
    """NaN f32 rows and bad q8 scales in ONE stream: both engines agree
    on every counter."""
    rng, flats, prev, pk = _round_inputs(3)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.1, dup_rate=0.1)
    poisoned, _, n_bad = _poison_f32(events, {(1, 2), (5, 5)})
    res = {c: run_engine_round(_cfg(compile=c), flats, prev, poisoned)
           for c in (False, True)}
    assert res[False].stats == res[True].stats
    assert res[False].stats.malformed_dropped == n_bad


# ---------------------------------------------------------------------------
# dedup not poisoned: retransmission after a malformed drop is accepted
# ---------------------------------------------------------------------------

def test_clean_retransmission_after_malformed_accepted():
    rng, flats, prev, pk = _round_inputs(11)
    events, _ = make_uplink_stream(rng, pk)       # lossless, no dups
    out = []
    injected = 0
    for packet, payload in events:
        if packet.kind is Kind.DATA and packet.client == 0:
            bad = np.asarray(payload).copy()
            bad[0] = np.nan
            out.append((packet, jnp.asarray(bad)))   # malformed first...
            injected += 1
        out.append((packet, payload))                # ...clean retransmit
    for compile_ in (False, True):
        cfg = _cfg(compile=compile_)
        got = run_engine_round(cfg, flats, prev, out)
        want = run_engine_round(cfg, flats, prev, events)
        _assert_rounds_equal(want, got)
        s = got.stats
        assert s.malformed_dropped == injected
        # the clean copies were NOT counted as duplicates
        assert s.duplicates_dropped == 0
        assert s.data_enqueued == want.stats.data_enqueued
        # client 0 is fully present despite every packet being poisoned
        assert float(np.asarray(got.up_mask)[0].sum()) == N


# ---------------------------------------------------------------------------
# the recycled candidate buffer: poisoned rows, then a smaller clean round
# ---------------------------------------------------------------------------

def _poison_around_first_copy(events, before, after):
    """A NaN copy of the (client, slot) DATA packets in ``before`` ahead
    of their first clean copy, and one of those in ``after`` right
    behind it (a poisoned duplicate of an accepted row)."""
    out, seen = [], set()
    for packet, payload in events:
        key = (packet.client, packet.index)
        first = packet.kind is Kind.DATA and key not in seen
        bad = None
        if first and key in before | after:
            bad = np.asarray(payload).copy()
            bad[1] = np.nan
            bad = (packet, jnp.asarray(bad))
        if bad is not None and key in before:
            out.append(bad)
        out.append((packet, payload))
        if bad is not None and key in after:
            out.append(bad)
        if packet.kind is Kind.DATA:
            seen.add(key)
    return out


def test_recycled_candidate_buffer_leaks_no_stale_row():
    """Round 1 stacks poisoned candidates; round 2, clean and with fewer
    candidates, stacks into a recycled buffer that still holds round
    1's rows past its own.  Both rounds' counters and globals equal the
    eager engine's, and round 2 counts nothing malformed."""
    from repro.core import engine_compiled as ec
    rng, flats, prev, pk = _round_inputs(17)
    events, _ = make_uplink_stream(rng, pk, dup_rate=0.3)
    first = _poison_around_first_copy(events, {(0, 0), (3, 2)},
                                      {(1, 4), (5, 9)})
    flats2 = flats[::-1] + 1.0
    pk2 = jax.vmap(lambda f: packetize(f, W))(flats2)
    second, _ = make_uplink_stream(rng, pk2, loss_rate=0.4)
    assert (sum(p.kind is Kind.DATA for p, _ in second)
            < sum(p.kind is Kind.DATA for p, _ in first))
    g = {c: prev for c in (False, True)}
    for r, (f, events) in enumerate(((flats, first), (flats2, second))):
        res = {}
        for compile_ in (False, True):
            before = ec.host_pool_stats()["reused"]
            res[compile_] = run_engine_round(_cfg(compile=compile_), f,
                                             g[compile_], events)
            g[compile_] = res[compile_].new_global
        _assert_rounds_equal(res[False], res[True])
        assert res[False].stats == res[True].stats
        s = res[True].stats
        assert s.malformed_dropped == (4 if r == 0 else 0)
        assert np.isfinite(np.asarray(g[True])).all()
    assert ec.host_pool_stats()["reused"] > before


# ---------------------------------------------------------------------------
# conservation identity with the new bucket
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compile_", [False, True])
def test_conservation_identity_includes_malformed(compile_):
    rng, flats, prev, pk = _round_inputs(5)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.4)
    poisoned, _, n_bad = _poison_f32(events, {(0, 1), (3, 4)})
    n_data = sum(e[0].kind is Kind.DATA for e in poisoned)
    res = run_engine_round(_cfg(compile=compile_), flats, prev, poisoned)
    s = res.stats
    assert (s.data_enqueued + s.duplicates_dropped + s.phase_dropped
            + s.late_dropped + s.malformed_dropped) == n_data
    assert s.malformed_dropped == n_bad


# ---------------------------------------------------------------------------
# async engines: dropped before the session-phase check, both paths agree
# ---------------------------------------------------------------------------

def test_async_malformed_parity_and_twin():
    rng, flats, prev, pk = _round_inputs(9)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.1, dup_rate=0.1)
    poisoned, clean, n_bad = _poison_f32(events, {(1, 0), (4, 2)})
    results = {}
    for compile_ in (False, True):
        cfg = _cfg(buffer_size=3, compile=compile_)
        got = run_async_engine(cfg, poisoned, prev)
        want = run_async_engine(cfg, clean, prev)
        assert got.stats.malformed_dropped == n_bad
        assert want.stats.malformed_dropped == 0
        np.testing.assert_array_equal(np.asarray(got.globals_),
                                      np.asarray(want.globals_))
        np.testing.assert_array_equal(np.asarray(got.state.global_),
                                      np.asarray(want.state.global_))
        assert np.isfinite(np.asarray(got.state.total)).all()
        results[compile_] = got
    assert results[False].stats == results[True].stats
    np.testing.assert_array_equal(np.asarray(results[False].state.global_),
                                  np.asarray(results[True].state.global_))


def test_async_malformed_q8_scale():
    rng, flats, prev, pk = _round_inputs(13)
    q8, sc = quantize_packets(pk)
    events, _ = make_uplink_stream(rng, q8, scales=sc)
    poisoned, clean, n_bad = _poison_q8_scale(events, {(2, 1)}, np.nan)
    for compile_ in (False, True):
        cfg = _cfg(buffer_size=2, compile=compile_)
        got = run_async_engine(cfg, poisoned, prev)
        want = run_async_engine(cfg, clean, prev)
        assert got.stats.malformed_dropped == n_bad
        np.testing.assert_array_equal(np.asarray(got.globals_),
                                      np.asarray(want.globals_))
