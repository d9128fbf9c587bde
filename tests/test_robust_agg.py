"""Byzantine-robust aggregation (DESIGN.md §11): parity + bound tests.

Three layers of contract:

1. **Differential**: for every ``agg_mode`` × wire (f32/q8) × demux
   (rr/slot) × shard count, the compiled round is bitwise the eager
   round over lossy/duplicated/out-of-order streams — the robust table
   fold reuses the scatter kernels through a combined ``slot·K +
   client`` index, so the established differential harness extends to
   it unchanged.  ``agg_mode='mean'`` is the pre-PR engine verbatim.
2. **Oracle**: on a fully-delivered round the fused finalize equals the
   straightforward numpy ``median`` / trimmed-mean over the client
   rows.
3. **Byzantine bound** (the ISSUE's property test): with ``f`` attackers
   present in a slot, ``f`` at or below the mode's breakdown point, the
   finalized value cannot leave the honest envelope (trimmed/median) or
   the ``tau`` influence ball (norm_clip) — while ``mean`` demonstrably
   escapes — under loss/dup/churn streams.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core.aggregation import quantize_packets
from repro.core.packets import packetize
from repro.core.protocol import Kind
from repro.core.rounds import (AttackConfig, ChurnConfig, apply_attack,
                               run_churn_rounds)
from repro.core.server import (AsyncServerEngine, EngineConfig,
                               ServerEngine, make_uplink_stream,
                               run_async_engine, run_engine_round)
from repro.kernels.packet_scatter import (norm_clip_weights,
                                          robust_finalize_jnp,
                                          robust_finalize_pallas)

K, P, W = 6, 480, 48
N = P // W


def _inputs(seed, int_valued=True):
    rng = np.random.default_rng(seed)
    draw = (rng.integers(-8, 9, (K, P)) if int_valued
            else rng.normal(size=(K, P)))
    flats = jnp.asarray(draw.astype(np.float32))
    prev = jnp.asarray(rng.integers(-8, 9, P).astype(np.float32))
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    return rng, flats, prev, pk


def _cfg(agg, **kw):
    base = dict(n_clients=K, n_params=P, payload=W, ring_capacity=7,
                agg_mode=agg, trim_beta=0.2, clip_tau=5.0)
    base.update(kw)
    return EngineConfig(**base)


def _assert_rounds_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.new_global),
                                  np.asarray(b.new_global))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.up_mask),
                                  np.asarray(b.up_mask))
    assert a.stats == b.stats


MODES = ["mean", "trimmed_mean", "median", "norm_clip"]


# ---------------------------------------------------------------------------
# 1. Differential: eager == compiled, every mode x wire x demux
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("agg", MODES)
@pytest.mark.parametrize("assign", ["rr", "slot"])
@pytest.mark.parametrize("wire", ["f32", "q8"])
def test_compiled_bitwise_matches_eager(agg, assign, wire):
    rng, flats, prev, pk = _inputs(42, int_valued=(wire == "f32"))
    weights = jnp.asarray(rng.integers(1, 4, K).astype(np.float32))
    sc = None
    if wire == "q8":
        pk, sc = quantize_packets(pk)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.3, dup_rate=0.3,
                                   scales=sc)
    eager = run_engine_round(_cfg(agg, ring_assign=assign), flats, prev,
                             events, weights=weights)
    comp = run_engine_round(_cfg(agg, ring_assign=assign, compile=True),
                            flats, prev, events, weights=weights)
    _assert_rounds_equal(eager, comp)


@pytest.mark.parametrize("agg", ["trimmed_mean", "median"])
@pytest.mark.parametrize("shards", [2, 4])
def test_table_modes_sharded_bitwise(agg, shards):
    """The combined-index table fold shards like any schedule — and
    because every (slot, client) row is written exactly once, the
    psum of zero-initialized partials reproduces the table bitwise at
    ANY shard count, even on non-integer payloads (0 + row == row)."""
    rng, flats, prev, pk = _inputs(9, int_valued=False)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.25, dup_rate=0.2)
    eager = run_engine_round(_cfg(agg), flats, prev, events)
    comp = run_engine_round(_cfg(agg, compile=True, shards=shards),
                            flats, prev, events)
    _assert_rounds_equal(eager, comp)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**16), loss=st.floats(0.0, 0.6),
       dup=st.floats(0.0, 0.5),
       agg=st.sampled_from(["trimmed_mean", "median", "norm_clip"]))
def test_robust_matches_eager_any_pattern(seed, loss, dup, agg):
    """Property: ANY loss/dup pattern, robust modes stay bitwise."""
    rng, flats, prev, pk = _inputs(seed)
    events, _ = make_uplink_stream(rng, pk, loss_rate=loss, dup_rate=dup)
    eager = run_engine_round(_cfg(agg), flats, prev, events)
    comp = run_engine_round(_cfg(agg, compile=True), flats, prev, events)
    _assert_rounds_equal(eager, comp)


@pytest.mark.parametrize("agg", ["trimmed_mean", "median", "norm_clip"])
def test_per_packet_compile_api_matches_bulk(agg):
    """ServerEngine(compile=True) records clients per pending packet;
    its dispatched robust round must equal the bulk demux and eager."""
    rng, flats, prev, pk = _inputs(23)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.2)
    eager_e = ServerEngine(_cfg(agg))
    comp_e = ServerEngine(_cfg(agg, compile=True))
    for packet, payload in events:
        eager_e.rx(packet, payload)
        comp_e.rx(packet, payload)
    ge, ce = eager_e.finalize_round(prev)
    gc, cc = comp_e.finalize_round(prev)
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(gc))
    np.testing.assert_array_equal(np.asarray(ce), np.asarray(cc))
    bulk = run_engine_round(_cfg(agg, compile=True), flats, prev, events)
    np.testing.assert_array_equal(np.asarray(ge),
                                  np.asarray(bulk.new_global))


def test_async_norm_clip_bitwise():
    """agg_mode='norm_clip' composes after staleness weighting in both
    async engines, bitwise."""
    rng, flats, prev, pk = _inputs(5)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.15, dup_rate=0.1)
    kw = dict(buffer_size=3, agg_mode="norm_clip", clip_tau=4.0,
              staleness_mode="poly", staleness_alpha=1.0)
    re_ = run_async_engine(_cfg("norm_clip", **{k: v for k, v in kw.items()
                                                if k != "agg_mode"}),
                           events, prev)
    rc = run_async_engine(
        _cfg("norm_clip", compile=True,
             **{k: v for k, v in kw.items() if k != "agg_mode"}),
        events, prev)
    assert re_.globals_.shape == rc.globals_.shape
    assert bool(jnp.all(re_.globals_ == rc.globals_))
    assert bool(jnp.all(re_.state.global_ == rc.state.global_))
    assert re_.stats == rc.stats


# ---------------------------------------------------------------------------
# 2. Oracle: fused finalize == numpy reference
# ---------------------------------------------------------------------------

def test_median_equals_numpy_on_full_round():
    """Lossless round, odd client count: the finalize is np.median."""
    k = 5
    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(k, P)).astype(np.float32))
    prev = jnp.zeros(P, jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    events, _ = make_uplink_stream(rng, pk)
    cfg = EngineConfig(n_clients=k, n_params=P, payload=W,
                       ring_capacity=7, agg_mode="median")
    res = run_engine_round(cfg, flats, prev, events)
    want = np.median(np.asarray(flats), axis=0)
    np.testing.assert_allclose(np.asarray(res.new_global), want,
                               rtol=0, atol=0)


def test_trimmed_mean_equals_numpy_on_full_round():
    rng = np.random.default_rng(1)
    flats = jnp.asarray(rng.normal(size=(K, P)).astype(np.float32))
    prev = jnp.zeros(P, jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    events, _ = make_uplink_stream(rng, pk)
    beta = 0.2                                 # t = floor(0.2 * 6) = 1
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                       ring_capacity=7, agg_mode="trimmed_mean",
                       trim_beta=beta)
    res = run_engine_round(cfg, flats, prev, events)
    vals = np.sort(np.asarray(flats), axis=0)[1:-1]   # drop min + max
    want = vals.mean(axis=0)
    np.testing.assert_allclose(np.asarray(res.new_global), want,
                               rtol=1e-6, atol=1e-6)


def test_finalize_pallas_interpret_matches_jnp():
    """The rank-select kernel and the sort-based twin agree bitwise on
    integer tables (same kept-value multiset, exact sums)."""
    rng = np.random.default_rng(3)
    S, k = 16, 8
    table = jnp.asarray(rng.integers(-8, 9, (S, k, W)).astype(np.float32))
    pres = jnp.asarray((rng.random((S, k)) < 0.7).astype(np.float32))
    table = table * pres[:, :, None]
    for median, beta in [(False, 0.2), (True, 0.0), (False, 0.45)]:
        aj, mj = robust_finalize_jnp(table, pres, median=median, beta=beta)
        ap, mp = robust_finalize_pallas(table, pres, median=median,
                                        beta=beta, interpret=True)
        np.testing.assert_array_equal(np.asarray(aj), np.asarray(ap))
        np.testing.assert_array_equal(np.asarray(mj), np.asarray(mp))


def _bench_reference(name):
    path = (Path(__file__).resolve().parents[1] / "bench" / "references"
            / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_pallas_round_matches_the_bench_trimmed_reference():
    """The served trimmed-mean round with the TPU body (the table fold
    and the rank-select finalize as Pallas kernels, interpreted here) at
    K = 10 and the benchmark's beta, against the benchmark's plain
    float64 reference: slots that all ten delivered trim one value a
    side, the rest none, and a burst nobody delivered keeps the
    previous global exactly."""
    ref = _bench_reference("trimmed_mean")
    k, n, w = 10, 24, 367
    rng = np.random.default_rng(15)
    pk = rng.normal(0.0, 0.05, (k, n, w)).astype(np.float32)
    events, up = make_uplink_stream(rng, jnp.asarray(pk), loss_rate=0.05,
                                    dup_rate=0.1)
    # a burst: slots 5 and 6 reach the server from nobody
    events = [e for e in events
              if not (e[0].kind == Kind.DATA and e[0].index in (5, 6))]
    up = np.asarray(up) > 0
    up[:, 5:7] = False
    prev = rng.normal(0.0, 0.05, n * w).astype(np.float32)
    cfg = EngineConfig(n_clients=k, n_params=n * w, payload=w,
                       ring_capacity=64, agg_mode="trimmed_mean",
                       compile=True, scan_body="pallas")
    assert cfg.trim_beta == ref.BETA
    res = run_engine_round(cfg, None, jnp.asarray(prev), events)
    want = ref.round_reference(pk, None, up)
    counts = np.asarray(res.counts)
    np.testing.assert_array_equal(counts, want.counts)
    assert {0, 9, 10} <= set(counts.tolist())
    value, scale = next(ref.chain([want], prev, [0], n * w))
    # a float32 sum of at most K kept values, then one divide: at most
    # K roundings of 2**-24 in units of the mean |kept value|
    assert ref.gap(np.asarray(res.new_global), value, scale) <= k * 2.0 ** -24


def test_norm_clip_weights_identity_inside_ball():
    """Rows with norm <= tau pass with factor exactly 1.0 — norm_clip
    degenerates to mean on bounded updates, bitwise."""
    rng = np.random.default_rng(4)
    rows = jnp.asarray(rng.normal(size=(32, W)).astype(np.float32))
    nrm = np.linalg.norm(np.asarray(rows), axis=1)
    w = jnp.asarray(rng.random(32).astype(np.float32))
    out = norm_clip_weights(w, rows, tau=float(nrm.max()) * 2.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w))


# ---------------------------------------------------------------------------
# 3. Config validation
# ---------------------------------------------------------------------------

def test_agg_mode_validation():
    with pytest.raises(ValueError, match="agg_mode"):
        _cfg("krum")
    with pytest.raises(ValueError, match="trim_beta"):
        _cfg("trimmed_mean", trim_beta=0.5)
    with pytest.raises(ValueError, match="trim_beta"):
        _cfg("trimmed_mean", trim_beta=-0.1)
    with pytest.raises(ValueError, match="clip_tau"):
        _cfg("norm_clip", clip_tau=0.0)
    with pytest.raises(ValueError, match="async"):
        _cfg("trimmed_mean", buffer_size=3)
    with pytest.raises(ValueError, match="async"):
        _cfg("median", buffer_size=3)
    _cfg("norm_clip", buffer_size=3)          # norm_clip composes: ok


# ---------------------------------------------------------------------------
# 4. Byzantine bound: f attackers below breakdown cannot escape;
#    mean demonstrably breaks
# ---------------------------------------------------------------------------

BIG = 1.0e6


def _attacked_round(seed, agg, *, f, boost=BIG, beta=0.3, tau=5.0,
                    loss=0.25, dup=0.15):
    """One lossy/dup round with f boosted attackers; returns the
    finalized per-slot grid, the per-slot presence mask (K, N), and the
    honest packet values."""
    rng = np.random.default_rng(seed)
    flats = jnp.asarray(rng.integers(-8, 9, (K, P)).astype(np.float32))
    prev = jnp.zeros(P, jnp.float32)
    pk = jax.vmap(lambda f_: packetize(f_, W))(flats)
    att = AttackConfig(model="scale", n_attackers=f, boost=boost)
    pk_att = apply_attack(rng, pk, att)
    events, _ = make_uplink_stream(rng, pk_att, loss_rate=loss,
                                   dup_rate=dup)
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                       ring_capacity=7, agg_mode=agg, trim_beta=beta,
                       clip_tau=tau, compile=True)
    res = run_engine_round(cfg, flats, prev, events)
    grid = np.asarray(packetize(res.new_global, W))     # (N, W)
    up = np.asarray(res.up_mask)                        # (K, N)
    return grid, up, np.asarray(pk), att.mask(K)


@pytest.mark.parametrize("agg", ["trimmed_mean", "median"])
def test_rank_modes_stay_in_honest_envelope(agg):
    """Where the slot's attacker count is at or below the trim depth,
    the finalized coordinate lies in [honest min, honest max] — the
    boosted values (1e6 x) are rank-trimmed out."""
    f = 2 if agg == "median" else 1
    beta = 0.3
    checked = 0
    for seed in range(3):
        grid, up, pk, att_mask = _attacked_round(seed, agg, f=f, beta=beta)
        for s in range(N):
            present = up[:, s] > 0
            m = int(present.sum())
            if m == 0:
                continue
            f_s = int((present & att_mask).sum())
            t_s = ((m - 1) // 2 if agg == "median"
                   else int(np.floor(beta * m)))
            honest = pk[present & ~att_mask, s]          # (h, W)
            if f_s > t_s or honest.shape[0] == 0:
                continue                  # above breakdown: no guarantee
            checked += 1
            lo, hi = honest.min(axis=0), honest.max(axis=0)
            assert (grid[s] >= lo - 1e-4).all(), (agg, seed, s)
            assert (grid[s] <= hi + 1e-4).all(), (agg, seed, s)
    assert checked > 10                   # the property was exercised


def test_norm_clip_bounds_attacker_influence():
    """Per slot the aggregate is Σ eff_w·row / Σ eff_w with every term's
    contribution norm capped at w·tau, so ‖agg‖ ≤ tau·m / Σ_honest
    min(1, tau/‖row‖) — a bound computed from HONEST rows only, i.e.
    independent of the attacker's 1e6 boost.  (Dropping the attackers'
    positive eff_w from the denominator only loosens it.)"""
    tau = 5.0
    checked = 0
    for seed in range(3):
        grid, up, pk, att_mask = _attacked_round(seed, "norm_clip", f=2,
                                                 tau=tau)
        for s in range(N):
            present = up[:, s] > 0
            m = int(present.sum())
            honest = pk[present & ~att_mask, s]
            if m == 0 or honest.shape[0] == 0:
                continue
            checked += 1
            hn = np.linalg.norm(honest, axis=1)
            denom = np.minimum(1.0, tau / np.maximum(hn, 1e-30)).sum()
            bound = tau * m / denom + 1e-3
            assert np.linalg.norm(grid[s]) <= bound, (seed, s)
            # the boosted rows would put the *unclipped* mean far outside
            assert bound < BIG
    assert checked > 10


def test_mean_demonstrably_breaks():
    """The same attacked stream through agg_mode='mean' escapes the
    honest envelope by orders of magnitude — the robustness the table
    modes buy is real, not vacuous."""
    grid, up, pk, att_mask = _attacked_round(0, "mean", f=2)
    att_hit = (up[att_mask] > 0).any(axis=0)             # slots attacked
    assert att_hit.any()
    honest_cap = np.abs(pk).max()                        # <= 8
    assert np.abs(grid[att_hit]).max() > 1000 * honest_cap


def test_churn_driver_attack_sweep_end_to_end():
    """run_churn_rounds(attack=...) with a robust mode keeps the served
    global bounded under churn + stragglers; mean blows up."""
    rng_ = np.random.default_rng(7)
    flats = jnp.asarray(rng_.integers(-4, 5, (K, P)).astype(np.float32))
    prev = jnp.zeros(P, jnp.float32)
    churn = ChurnConfig(participation=0.9, straggle_rate=0.15,
                        loss_rate=0.1, dup_rate=0.05)
    att = AttackConfig(model="scale", n_attackers=1, boost=1e4)

    def run(agg):
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=7, compile=True, agg_mode=agg,
                           trim_beta=0.25, min_clients=1)
        hist = run_churn_rounds(cfg, churn, flats, prev, 3,
                                rng=np.random.default_rng(11),
                                attack=att)
        return np.asarray(hist.final_global)

    honest_mean = np.asarray(flats).mean(axis=0)
    err_robust = np.abs(run("trimmed_mean") - honest_mean).max()
    err_mean = np.abs(run("mean") - honest_mean).max()
    # the 1e4-boosted client drags the plain mean orders of magnitude
    # off the honest average; trimmed-mean stays in the honest range
    assert err_mean > 100.0
    assert err_robust < 20.0
    assert err_robust < err_mean


def test_mean_mode_default_unchanged():
    """EngineConfig() defaults to agg_mode='mean' and robust fields do
    not perturb the mean path: identical result with any tau/beta."""
    rng, flats, prev, pk = _inputs(13)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.2, dup_rate=0.2)
    assert EngineConfig(n_clients=K, n_params=P, payload=W).agg_mode \
        == "mean"
    a = run_engine_round(_cfg("mean", trim_beta=0.1, clip_tau=1.0),
                         flats, prev, events)
    b = run_engine_round(_cfg("mean", trim_beta=0.4, clip_tau=99.0),
                         flats, prev, events)
    _assert_rounds_equal(a, b)
