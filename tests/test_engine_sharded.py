"""Sharded round engine: shard-count invariance + race accounting.

The load-bearing acceptance properties (ISSUE 4 + DESIGN.md §7):

1. ``EngineConfig(shards=k)`` for k ∈ {1, 2, 4, 8} is **bitwise
   identical** to the unsharded compiled engine on integer-valued
   payloads — exact AND approx mode, both demux policies, lossy /
   duplicated / out-of-order streams.  Approx equality is the strong
   check: it holds only because ``shard_schedule`` keeps every drain
   batch (the last-writer-wins race window) intact on one shard.
2. The schedule demux is a partition: every live batch lands on the
   shard owning its worker ring, padding is inert, nothing is dropped.
3. Race accounting: per-shard approx-mode lost updates sum to the
   unsharded total (sharding splits the race ≈ 1/N per shard, it does
   not change it).
4. The same parity holds over a *real* ``('worker',)`` device mesh —
   exercised in-process when the suite runs under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (CI's
   multi-device lane) and via a subprocess otherwise.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine_compiled as ec
from repro.core.packets import packetize
from repro.core.server import (EngineConfig, ServerEngine,
                               make_uplink_stream, run_engine_round)
from repro.runtime.sharding import WORKER_AXIS, worker_ctx, worker_mesh


def _round_inputs(seed, k=6, p=480, w=48):
    rng = np.random.default_rng(seed)
    flats = jnp.asarray(rng.integers(-8, 9, (k, p)).astype(np.float32))
    prev = jnp.asarray(rng.integers(-8, 9, p).astype(np.float32))
    pk = jax.vmap(lambda f: packetize(f, w))(flats)
    return rng, flats, prev, pk


def _assert_rounds_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.new_global),
                                  np.asarray(b.new_global))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.up_mask),
                                  np.asarray(b.up_mask))
    if a.new_client_flats is not None:
        np.testing.assert_array_equal(np.asarray(a.new_client_flats),
                                      np.asarray(b.new_client_flats))


@pytest.mark.parametrize("mode", ["exact", "approx"])
@pytest.mark.parametrize("assign", ["rr", "slot"])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_sharded_bitwise_matches_unsharded(mode, assign, shards):
    """The acceptance criterion: any shard count is bitwise the
    unsharded compiled engine — approx included, because the drain
    batches (race windows) are demuxed whole."""
    rng, flats, prev, pk = _round_inputs(42)
    weights = jnp.asarray(rng.integers(1, 4, 6).astype(np.float32))
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.3, dup_rate=0.3)
    down = jnp.asarray((rng.random((6, pk.shape[1])) > 0.2)
                       .astype(np.float32))
    kw = dict(n_clients=6, n_params=480, payload=48, ring_capacity=7,
              mode=mode, ring_assign=assign, compile=True)
    base = run_engine_round(EngineConfig(**kw), flats, prev, events,
                            down_mask=down, weights=weights)
    got = run_engine_round(EngineConfig(shards=shards, **kw), flats, prev,
                           events, down_mask=down, weights=weights)
    _assert_rounds_equal(base, got)


@pytest.mark.parametrize("cap", [1, 7, 32])
def test_sharded_matches_eager_engine(cap):
    """Transitively with test_engine_compiled parity: sharded compiled
    == unsharded compiled == eager — checked directly here across
    ragged ring capacities."""
    rng, flats, prev, pk = _round_inputs(7)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.25, dup_rate=0.25)
    kw = dict(n_clients=6, n_params=480, payload=48, ring_capacity=cap,
              mode="exact")
    eager = run_engine_round(EngineConfig(**kw), flats, prev, events)
    shard = run_engine_round(EngineConfig(compile=True, shards=4, **kw),
                             flats, prev, events)
    _assert_rounds_equal(eager, shard)


def test_per_packet_api_with_shards():
    """ServerEngine(compile=True, shards=k) keeps the per-packet rx API
    and finalizes through the sharded dispatch, bitwise."""
    rng, flats, prev, pk = _round_inputs(23, k=5, p=300, w=30)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.2)
    down = jnp.asarray((rng.random((5, pk.shape[1])) > 0.2)
                       .astype(np.float32))
    kw = dict(n_clients=5, n_params=300, payload=30, ring_capacity=8)
    base = run_engine_round(EngineConfig(compile=True, **kw), flats, prev,
                            events, down_mask=down)
    engine = ServerEngine(EngineConfig(compile=True, shards=4, **kw))
    for packet, payload in events:
        engine.rx(packet, payload)
    ng, cnt, nf = engine.finalize_and_distribute(prev, flats, down)
    np.testing.assert_array_equal(np.asarray(base.new_global),
                                  np.asarray(ng))
    np.testing.assert_array_equal(np.asarray(base.counts), np.asarray(cnt))
    np.testing.assert_array_equal(np.asarray(base.new_client_flats),
                                  np.asarray(nf))


def test_overlapped_sharded_rounds_match_sequential():
    """The double-buffered multi-round driver keeps its overlap contract
    under sharding."""
    rng, flats, prev, pk = _round_inputs(9, k=4, p=320, w=32)
    cfg = EngineConfig(n_clients=4, n_params=320, payload=32,
                       ring_capacity=8, compile=True, shards=4)
    rounds = []
    for r in range(3):
        f = jnp.asarray(
            np.random.default_rng(100 + r).integers(-8, 9, (4, 320))
            .astype(np.float32))
        ev, _ = make_uplink_stream(rng, jax.vmap(
            lambda x: packetize(x, 32))(f), loss_rate=0.2, dup_rate=0.2)
        rounds.append((ev, f, None))
    overlapped = ec.run_compiled_rounds(cfg, rounds, prev)
    g = prev
    for (ev, f, _), got in zip(rounds, overlapped):
        want = run_engine_round(cfg, f, g, ev)
        _assert_rounds_equal(want, got)
        g = want.new_global


# ---------------------------------------------------------------------------
# Schedule demux properties
# ---------------------------------------------------------------------------

def _demuxed_schedule(seed=0, n_workers=5, ring_assign="rr", cap=7):
    rng, flats, prev, pk = _round_inputs(seed)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.3)
    cfg = EngineConfig(n_clients=6, n_params=480, payload=48,
                       ring_capacity=cap, n_workers=n_workers,
                       ring_assign=ring_assign, compile=True)
    sched, _, _ = ec.demux_events(cfg, events)
    return sched


@pytest.mark.parametrize("shards", [1, 2, 3, 4, 8])
def test_shard_schedule_is_a_partition(shards):
    """Every live batch lands exactly once, on the shard that owns its
    worker ring; padding rows/shards are inert."""
    sched = _demuxed_schedule()
    idx, w, pk, _, _ = ec.shard_schedule(sched, shards)
    assert idx.shape[0] == shards
    # live (slot, weight) entries are conserved: multiset of scheduled
    # arrivals is identical before and after the demux
    def arrivals(i2, w2):
        m = i2 >= 0
        return sorted(zip(i2[m].ravel().tolist(), w2[m].ravel().tolist()))
    flat_idx = idx.reshape(-1, idx.shape[-1])
    flat_w = w.reshape(-1, w.shape[-1])
    assert arrivals(flat_idx, flat_w) == arrivals(sched.idx, sched.weights)
    # ring ownership: per-shard batches only come from workers mapped to
    # that shard (match rows back by content)
    live = sched.workers[:sched.n_batches]
    for s in range(shards):
        for r in range(idx.shape[1]):
            if (idx[s, r] >= 0).any():
                src = np.nonzero((sched.idx == idx[s, r]).all(1))[0]
                assert any(live[i] % shards == s for i in src
                           if i < sched.n_batches)
    # payload rows ride with their batch
    total_pk = pk.reshape(-1, pk.shape[-2], pk.shape[-1]).sum(axis=0)
    np.testing.assert_allclose(total_pk.sum(),
                               sched.payloads[:sched.n_batches].sum(),
                               rtol=1e-6)


def test_shard_schedule_more_shards_than_workers():
    """shards > n_workers leaves the excess shards inert (the effective
    parallelism floor documented on EngineConfig.shards)."""
    sched = _demuxed_schedule(n_workers=2)
    idx, w, pk, _, _ = ec.shard_schedule(sched, 8)
    for s in range(2, 8):
        assert (idx[s] == -1).all() and (w[s] == 0).all()


def test_shard_schedule_empty_round():
    sched = ec.build_drain_schedule(
        np.zeros(0, np.int32), np.zeros(0, np.float32),
        np.zeros((0, 16), np.float32), n_workers=3, ring_capacity=4)
    idx, w, pk, _, _ = ec.shard_schedule(sched, 4)
    assert (idx == -1).all() and (w == 0).all() and (pk == 0).all()


# ---------------------------------------------------------------------------
# Approx-mode race accounting per shard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_race_accounting_is_conserved_across_shards(shards):
    """Per-shard lost-update counts sum to the unsharded total: the
    sharded engine splits the race window across shards, it does not
    change the global race."""
    sched = _demuxed_schedule(ring_assign="slot", cap=16)
    per_shard = ec.approx_lost_updates(sched, shards)
    assert per_shard.shape == (shards,)
    assert per_shard.sum() == ec.approx_lost_updates(sched, 1).sum()


def test_race_accounting_matches_measured_loss():
    """The accounting equals the measured exact-vs-approx count of
    surviving adds: exact adds every arrival, approx drops exactly the
    lost updates."""
    rng, flats, prev, pk = _round_inputs(11)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.1, dup_rate=0.3)
    kw = dict(n_clients=6, n_params=480, payload=48, ring_capacity=16,
              ring_assign="slot", compile=True)
    sched, _, _ = ec.demux_events(EngineConfig(mode="approx", **kw), events)
    lost = int(ec.approx_lost_updates(sched, 1).sum())
    # measure: unit payloads/weights make the surviving-add count
    # readable straight off the aggregate sum
    ones = [(p_, None if pay is None else np.ones_like(pay))
            for p_, pay in events]
    ex = run_engine_round(EngineConfig(mode="exact", **kw),
                          jnp.ones_like(flats), prev, ones)
    ap = run_engine_round(EngineConfig(mode="approx", **kw),
                          jnp.ones_like(flats), prev, ones)
    slots = ex.counts.shape[0]
    # per-slot sums: exact = count_i, approx = survivors_i; both divide
    # by count_i, so recover survivors from the approx average
    surv = np.asarray(ap.counts) * np.asarray(
        ap.new_global).reshape(slots, -1)[:, 0]
    exact_adds = np.asarray(ex.counts) * np.asarray(
        ex.new_global).reshape(slots, -1)[:, 0]
    assert int(round(float(exact_adds.sum() - surv.sum()))) == lost
    assert lost > 0      # the slot-demux stress stream really races


# ---------------------------------------------------------------------------
# Worker mesh
# ---------------------------------------------------------------------------

def test_worker_mesh_requires_devices():
    n = jax.device_count()
    assert worker_mesh(n + 1) is None
    assert worker_mesh(1) is None            # unsharded: no mesh needed
    if n > 1:
        ctx = worker_ctx(n)
        assert ctx is not None and ctx.worker_axis == WORKER_AXIS
        assert ctx.axis_size(WORKER_AXIS) == n


@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_cpu_emulates_shards_beyond_device_count(mode):
    """On the CPU a shard count above the device count still runs, as
    the single-device emulation, bitwise equal to the unsharded round."""
    shards = jax.device_count() + 1
    assert worker_mesh(shards) is None
    rng, flats, prev, pk = _round_inputs(11)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.1, dup_rate=0.2)
    kw = dict(n_clients=6, n_params=480, payload=48, ring_capacity=7,
              n_workers=shards, mode=mode, compile=True)
    base = run_engine_round(EngineConfig(**kw), flats, prev, events)
    got = run_engine_round(EngineConfig(shards=shards, **kw), flats, prev,
                           events)
    _assert_rounds_equal(base, got)


class _FakeChip:
    platform = "tpu"


def test_accelerator_refuses_emulated_shards(monkeypatch):
    """On an accelerator too few devices is an error that names the
    request, never a silent single-device emulation."""
    from repro.runtime import sharding
    monkeypatch.setattr(sharding.jax, "devices", lambda: [_FakeChip()])
    sharding.worker_mesh.cache_clear()
    sharding.host_worker_mesh.cache_clear()
    try:
        with pytest.raises(ValueError, match="shards=4"):
            sharding.worker_mesh(4)
        with pytest.raises(ValueError, match="hosts=2, shards=2"):
            sharding.host_worker_mesh(2, 2)
        assert sharding.worker_mesh(1) is None      # unsharded: no mesh
    finally:
        sharding.worker_mesh.cache_clear()
        sharding.host_worker_mesh.cache_clear()


def test_shards_require_compiled_engine():
    with pytest.raises(ValueError):
        EngineConfig(n_clients=2, n_params=64, payload=16, shards=2)
    with pytest.raises(ValueError):
        EngineConfig(n_clients=2, n_params=64, payload=16, shards=0,
                     compile=True)


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="suite already runs on a real 8-device mesh")
def test_real_mesh_parity_subprocess():
    """Bitwise parity over a *real* shard_map mesh: spawn a fresh
    interpreter with 8 forced host devices (XLA_FLAGS is read at jax
    init, so it cannot be flipped in-process)."""
    prog = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "assert jax.device_count() == 8, jax.device_count()\n"
        "from repro.core.packets import packetize\n"
        "from repro.core.server import (EngineConfig, make_uplink_stream,\n"
        "                               run_engine_round)\n"
        "from repro.runtime.sharding import worker_mesh\n"
        "assert worker_mesh(8) is not None\n"
        "rng = np.random.default_rng(1)\n"
        "flats = jnp.asarray(rng.integers(-8, 9, (4, 256))\n"
        "                    .astype(np.float32))\n"
        "prev = jnp.zeros((256,), jnp.float32)\n"
        "pk = jax.vmap(lambda f: packetize(f, 32))(flats)\n"
        "ev, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.3)\n"
        "for mode in ('exact', 'approx'):\n"
        "    kw = dict(n_clients=4, n_params=256, payload=32,\n"
        "              ring_capacity=8, n_workers=8, mode=mode,\n"
        "              compile=True)\n"
        "    base = run_engine_round(EngineConfig(**kw), flats, prev, ev)\n"
        "    got = run_engine_round(EngineConfig(shards=8, **kw), flats,\n"
        "                           prev, ev)\n"
        "    np.testing.assert_array_equal(np.asarray(base.new_global),\n"
        "                                  np.asarray(got.new_global))\n"
        "    np.testing.assert_array_equal(np.asarray(base.counts),\n"
        "                                  np.asarray(got.counts))\n"
        "print('MESH_PARITY_OK')\n")
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=8"),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_PARITY_OK" in out.stdout
