"""The trimmed-mean cell, ``paper-cnn-k10-trimmed.lossy``, cut to a CPU
size: its plain reference against a brute-force per-coordinate sort, a
sound run, the faults its check must catch, the control one precision
step lower, the trim fraction the configuration, the reference and the
engine share, and the readers of the robust round's device kernels on
a recorded chip trace.

The cut keeps the cell's K = 10, so a round holds slots at trim depth
1 (all ten clients delivered), at depth 0 (some packet lost) and with
no contributor at all (the traffic's bursts)."""
import dataclasses

import jax
import numpy as np
import pytest

from benchutil import BENCH, harness
from test_bench_faults import broken

import calibrate  # noqa: E402  (benchutil puts bench/ on the path)
import kernel_time
import trace_reduce

CELL = "paper-cnn-k10-trimmed.lossy"
SMALL = dict(n_params=367 * 40 - 11)


def small_cell(**extra):
    return harness.load_cell(CELL, overrides=dict(SMALL, **extra))


def reference():
    return harness.load_module("references", "trimmed_mean")


def brute_force(payloads, scales, accepted, beta):
    """Per slot and coordinate: sort the delivered values, drop
    floor(beta * m) from each side, average the rest (0 where m = 0)."""
    k, n, w = payloads.shape
    x = payloads.astype(np.float64)
    if scales is not None:
        x = x * scales[:, :, None]
    out = np.zeros((n, w))
    for s in range(n):
        who = np.flatnonzero(accepted[:, s])
        m = len(who)
        t = int(np.floor(beta * m))
        for c in range(w):
            if m:
                out[s, c] = np.sort(x[who, s, c])[t:m - t].mean()
    return out


@pytest.mark.parametrize("wire", ["f32", "q8"])
def test_reference_matches_a_brute_force_sort(wire):
    ref = reference()
    rng = np.random.default_rng(2 ** 31 + 77)
    k, n, w = 10, 30, 11
    if wire == "f32":
        payloads = rng.normal(0.0, 0.05, (k, n, w)).astype(np.float32)
        scales = None
    else:
        payloads = rng.integers(-127, 128, (k, n, w)).astype(np.int8)
        scales = (10.0 ** rng.uniform(-4, -2, (k, n))).astype(np.float32)
    accepted = rng.random((k, n)) > 0.1
    accepted[:, :4] = True                   # t = 1
    accepted[:, 4:6] = False                 # m = 0
    accepted[:, 6] = np.arange(k) >= 7       # m = 3, t = 0
    got = ref.round_reference(payloads, scales, accepted)
    m = accepted.sum(axis=0)
    assert {0, 3, 10} <= set(m.tolist()) and (got.counts == m).all()
    want = brute_force(payloads, scales, accepted, ref.BETA)
    np.testing.assert_allclose(got.mean.reshape(n, w), want,
                               rtol=1e-12, atol=0)
    # m = 0 marks its elements exact (scale 0); others carry a scale
    assert (got.scale.reshape(n, w)[m == 0] == 0).all()
    assert (got.scale.reshape(n, w)[m > 0] > 0).all()


def _run(cell):
    return harness.run(cell, 2 ** 31 + 3, 0.5, False, require_chip=False)


def test_sound_run_is_correct():
    line = _run(small_cell())
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0


@pytest.fixture
def trim_off_by_one(monkeypatch):
    """The finalize drops one value more from each side than it should;
    JAX's caches are cleared on both sides so that no compiled round
    from another test is reused, and none compiled here survives."""
    from repro.kernels import packet_scatter
    orig = packet_scatter._robust_trim
    monkeypatch.setattr(packet_scatter, "_robust_trim",
                        lambda m, **kw: orig(m, **kw) + 1.0)
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_untrimmed_mean_is_caught():
    line = _run(small_cell(agg_mode="mean"))
    assert not line["correct"]
    assert line["failed"] == line["attempted"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_is_caught(fault):
    with broken(fault):
        line = _run(small_cell())
    assert not line["correct"]
    assert line["failed"] == line["attempted"] > 0


def test_trim_depth_off_by_one_is_caught(trim_off_by_one):
    line = _run(small_cell())
    assert not line["correct"]
    assert line["failed"] == line["attempted"] > 0


def test_control_fails_the_limit():
    cell = small_cell()
    limit = cell.config["limits"]["fold_gap"]
    for seed in (3, 2 ** 31 + 11, 40):
        got = calibrate.readings(cell, seed, 2, require_chip=False)
        assert got["program"]["fold_gap"] <= limit
        assert got["control_fold_gap"] > limit


def test_config_reference_and_engine_share_the_trim_fraction():
    """The harness passes no ``trim_beta``: the round serves
    ``EngineConfig``'s default, which the configuration states and the
    reference trims by."""
    from repro.core.server import EngineConfig
    default = {f.name: f.default
               for f in dataclasses.fields(EngineConfig)}["trim_beta"]
    config = small_cell().config
    cfg = harness.engine_config(config)
    assert cfg.agg_mode == config["agg_mode"] == "trimmed_mean"
    assert config["trim_beta"] == reference().BETA == cfg.trim_beta == default


# ``fixtures/small_robust_round.xplane.pb`` is a trace recorded on one
# TPU v5e by the harness itself: four traced rounds of the cell cut to
# 40 slots of 367 f32 at K = 10, the table fold and the finalize
# running as Pallas kernels on the chip.  Its numbers are fixed here,
# so a change to a reader or to the reduction shows.
FIXTURE = BENCH / "tests" / "fixtures" / "small_robust_round.xplane.pb"
MEAN_FIXTURES = ("small_round.xplane.pb", "small_round_spans.xplane.pb")
ROUNDS = 4
# what the four traced rounds' schedules recorded: accepted packets
ACCEPTED = (227, 231, 227, 231)
FOLD_NS, FINALIZE_NS = 321333.0, 33033.0     # 32 and 4 kernel calls
READERS = ("table_fold_s", "table_fold_roofline", "robust_finalize_s",
           "robust_finalize_roofline")


def _obs(path, rounds):
    return harness.Observation(config={"n_clients": 10},
                               peaks=harness.peaks_for("TPU v5 lite"),
                               rounds=rounds, trace=trace_reduce.load(
                                   str(path)), window_compiles=0)


@pytest.fixture(scope="module")
def robust_obs():
    rounds = [dict(accepted=a, payload=367, wire_itemsize=4, q8=False,
                   n_slots=40) for a in ACCEPTED]
    return _obs(FIXTURE, rounds)


def test_fixture_holds_one_fold_a_batch_and_one_finalize_a_round(robust_obs):
    named = [(e.meta["module"], kernel_time.kernel_name(e))
             for e in robust_obs.trace.device_ops
             if kernel_time.kernel_name(e)]
    assert named.count(("jit__robust_round_device", "fold_f32")) == 8 * ROUNDS
    assert named.count(("jit__robust_round_device",
                        "robust_finalize")) == ROUNDS
    assert len(named) == 9 * ROUNDS


@pytest.mark.parametrize("metric, want", [
    ("table_fold_s", FOLD_NS * 1e-9 / ROUNDS),
    ("robust_finalize_s", FINALIZE_NS * 1e-9 / ROUNDS),
])
def test_kernel_seconds_on_the_recorded_trace(robust_obs, metric, want):
    got = harness.load_module("metrics", metric).read(robust_obs)
    assert got == pytest.approx(want, rel=1e-12)


def test_rooflines_on_the_recorded_trace(robust_obs):
    hbm = 819e9
    s, k, w = 40, 10, 367
    table = sum(a * (w * 4 + 4 + 4) + 2 * (s * k * w * 4 + s * k * 4)
                for a in ACCEPTED) / hbm
    finalize = ROUNDS * (s * k * (w + 1) * 4 + s * (w + 1) * 4) / hbm
    for metric, least, ns in [("table_fold_roofline", table, FOLD_NS),
                              ("robust_finalize_roofline", finalize,
                               FINALIZE_NS)]:
        got = harness.load_module("metrics", metric).read(robust_obs)
        assert got == pytest.approx(100.0 * least / (ns * 1e-9), rel=1e-12)
        assert 0 < got < 100


@pytest.mark.parametrize("fixture", MEAN_FIXTURES)
@pytest.mark.parametrize("metric", READERS)
def test_readers_read_none_on_mean_round_traces(fixture, metric):
    obs = _obs(BENCH / "tests" / "fixtures" / fixture, [{}] * ROUNDS)
    assert harness.load_module("metrics", metric).read(obs) is None
