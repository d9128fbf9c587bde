"""Checks of the demux's host buffer pool (``engine_compiled._HostPool``)
shared by the f32 and q8 wire tests: rounds of one shape reuse the
pool's buffers, and no buffer a caller or a transfer still holds is
leased again."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from _drain_reference import (assert_schedules_equal, reference_schedule,
                              schedule_copy)

from repro.core import engine_compiled as ec
from repro.core.aggregation import quantize_packets
from repro.core.packets import packetize
from repro.core.server import (EngineConfig, make_uplink_stream,
                               run_engine_round)


def pool_rounds(n, *, wire="f32", seed=21):
    """``n`` rounds of one shape on the ``wire``: the same loss,
    duplication and order (one stream seed), fresh integer payloads
    each round -> (cfg, [(events, flats)])."""
    cfg = EngineConfig(n_clients=10, n_params=1000, payload=64,
                       n_workers=3, ring_capacity=7, compile=True)
    rng = np.random.default_rng(seed)
    rounds = []
    for _ in range(n):
        flats = jnp.asarray(rng.integers(-8, 9, (10, 1000))
                            .astype(np.float32))
        pk = jax.vmap(lambda f: packetize(f, 64))(flats)
        scales = None
        if wire == "q8":
            pk, scales = quantize_packets(pk)
        events, _ = make_uplink_stream(np.random.default_rng(seed), pk,
                                       loss_rate=0.2, dup_rate=0.3,
                                       scales=scales)
        rounds.append((events, flats))
    return cfg, rounds


def assert_rounds_equal(a, b):
    for f in ("new_global", "counts", "up_mask"):
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f
    assert a.stats == b.stats


def pooled_rounds_match_reference(monkeypatch, wire):
    """Five rounds at one shape through ``run_compiled_round``: each
    leases one buffer per payload stack (f32: the candidate stack and
    the shipped rows; q8: the shipped rows), from the third round on
    every lease reuses a pooled buffer, and every round's schedule is
    bitwise ``build_drain_schedule``'s on its accepted arrivals and its
    global, counts, uplink mask and stats bitwise the eager engine's."""
    cfg, rounds = pool_rounds(5, wire=wire)
    seen = []
    demux = ec.demux_events

    def recording(*args):
        out = demux(*args)
        seen.append(schedule_copy(out[0]))   # a copy: holds no buffer
        return out
    monkeypatch.setattr(ec, "demux_events", recording)
    prev = jnp.zeros(cfg.n_params, jnp.float32)
    per_round = []
    for events, flats in rounds:
        before = ec.host_pool_stats()
        got = ec.run_compiled_round(cfg, flats, prev, events)
        after = ec.host_pool_stats()
        per_round.append((after["leases"] - before["leases"],
                          after["reused"] - before["reused"]))
        want = run_engine_round(dataclasses.replace(cfg, compile=False),
                                flats, prev, events)
        assert_rounds_equal(got, want)
        assert_schedules_equal(seen[-1], reference_schedule(cfg, events))
        prev = want.new_global
    leases = 1 if wire == "q8" else 2
    assert all(n == leases for n, _ in per_round), per_round
    assert all(n == r for n, r in per_round[2:]), per_round


def _no_shared_buffer(scheds) -> bool:
    return not any(np.shares_memory(a.payloads, b.payloads)
                   for a, b in itertools.combinations(scheds, 2))


def held_schedules_keep_their_rows(wire):
    """A caller holding the schedules of three consecutive
    ``demux_events`` calls finds each one's shipped rows and arrivals
    unchanged after the later calls, each bitwise the reference
    schedule: a held buffer is never leased again.  Once released, the
    next call reuses them."""
    cfg, rounds = pool_rounds(4, wire=wire, seed=22)
    held, copies = [], []
    for events, _ in rounds[:3]:
        sched = ec.demux_events(cfg, events)[0]
        held.append(sched)
        copies.append((schedule_copy(sched), np.array(sched.arrivals[2])))
    for i, (want, arrivals) in enumerate(copies):
        assert_schedules_equal(held[i], want)
        assert held[i].arrivals[2].tobytes() == arrivals.tobytes()
        assert_schedules_equal(held[i], reference_schedule(cfg,
                                                           rounds[i][0]))
    assert _no_shared_buffer(held)
    del held, sched
    before = ec.host_pool_stats()
    ec.demux_events(cfg, rounds[3][0])
    after = ec.host_pool_stats()
    assert after["reused"] - before["reused"] == (
        after["leases"] - before["leases"]) > 0
