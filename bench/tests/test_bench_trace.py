"""The reduction from a recorded chip trace to per-layer metrics.

``fixtures/small_round.xplane.pb`` is a trace recorded on one TPU v5e
by the harness itself: four traced rounds of a small f32 round (6
clients, 40 slots of 367 f32), the Pallas fold running on the chip.
Its numbers are fixed here, so a change to the reduction shows."""
import pytest

from benchutil import BENCH, harness

FIXTURE = BENCH / "tests" / "fixtures" / "small_round.xplane.pb"
ROUNDS = 4


@pytest.fixture(scope="module")
def obs():
    import trace_reduce
    tr = trace_reduce.load(str(FIXTURE))
    return harness.Observation(config={},
                               peaks=harness.peaks_for("TPU v5 lite"),
                               rounds=[{}] * ROUNDS, trace=tr,
                               window_compiles=0)


@pytest.mark.parametrize("metric, want", [
    ("fold_s", 62966e-9 / ROUNDS),            # 32 kernel calls, 8 a round
    ("device_idle_pct", 100.0 * (1.0 - 187860.0 / 18249538.0)),
    ("demux_s", (991689 + 901480 + 799800 + 1019449) * 1e-9 / ROUNDS),
])
def test_fixture_reduces_to_fixed_numbers(obs, metric, want):
    got = harness.load_module("metrics", metric).read(obs)
    assert got == pytest.approx(want, rel=1e-12)


def test_fixture_window_and_ops(obs):
    tr = obs.trace
    assert tr.n_devices == 1
    assert tr.window_ns() == 18249538.0
    fold = harness.load_module("metrics", "fold_s")
    kernels = [e for e in tr.device_ops if fold.is_fold(e)]
    assert len(kernels) == 32
    assert {e.meta["module"] for e in kernels} == {"jit__round_device"}
    # the loop that holds the kernel is busy time, not a top op
    names = [name for name, _ in tr.breakdown()["device_ops"]]
    assert not any("/while" in n for n in names)
    assert len(names) <= 10 and len(tr.breakdown()["idle_gaps"]) <= 10


def test_nothing_to_read_reads_none(obs):
    empty = harness.Observation(config={}, peaks=obs.peaks, rounds=[],
                                trace=obs.trace, window_compiles=0)
    for metric in ("fold_s", "fold_roofline", "demux_s", "h2d_mb",
                   "sched_useful_pct"):
        assert harness.load_module("metrics", metric).read(empty) is None
