"""The readers of the program's own phase spans (``fl.*``, opened in
``core/engine_compiled.py``): each sums its span over the traced
window and divides by the rounds traced, and reads None where the
program opens no such span.  ``h2d_s`` adds the runtime's layout of
the copies (``XlaLinearize``, on PjRt's threads) and the copy's flight
to its span.

``fixtures/small_round_spans.xplane.pb`` is a trace recorded on one TPU
v5e by the harness itself, with the program's spans in it: four traced
rounds of a small f32 round (10 clients, 200 slots of 367 f32), the
Pallas fold running on the chip.  Its numbers are fixed here, so a
change to a reader or to the reduction shows."""
import pytest

from benchutil import BENCH, harness

import trace_reduce

READERS = {"demux_scan_s": "fl.demux.scan", "demux_gate_s": "fl.demux.gate",
           "demux_stack_s": "fl.demux.stack",
           "demux_sched_s": "fl.demux.schedule", "h2d_s": "fl.h2d",
           "launch_s": "fl.launch"}


FIXTURE = BENCH / "tests" / "fixtures" / "small_round_spans.xplane.pb"
ROUNDS = 4
DEMUX_PHASES = ("demux_scan_s", "demux_gate_s", "demux_stack_s",
                "demux_sched_s")


def _obs(host, rounds=2, device_ops=()):
    tr = trace_reduce.Trace(window=(0.0, 1e9), device_ops=list(device_ops),
                            host=host, n_devices=1)
    return harness.Observation(config={}, peaks={}, rounds=[{}] * rounds,
                               trace=tr, window_compiles=0)


def _spans(durations, t0=0.0):
    """Events of the (name, duration) pairs, back to back from t0."""
    out, t = [], t0
    for name, dur in durations:
        out.append(trace_reduce.Event(name, t, dur, {}))
        t += dur
    return out


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_span_per_round(metric):
    span = READERS[metric]
    others = [s for s in READERS.values() if s != span]
    # round 1 opens the span once; round 2 twice, around another phase
    host = (_spans([("fl.round", 9e6)])
            + _spans([(span, 1500.0), (others[0], 7e3)], t0=10.0)
            + _spans([("fl.round", 9e6)], t0=1e7)
            + _spans([(span, 2000.0), (others[1], 3e3), (span, 500.0)],
                     t0=1e7 + 10.0)
            + _spans([("demux_events", 4e6), ("Transpose", 2e6)], t0=5e8))
    read = harness.load_module("metrics", metric).read
    assert read(_obs(host)) == pytest.approx((1500 + 2000 + 500) / 2e9,
                                             rel=1e-12)
    without = [e for e in host if e.name != span]
    assert read(_obs(without)) is None
    assert read(_obs(host, rounds=0)) is None


def test_h2d_s_adds_the_runtime_layout_and_the_copy_once():
    read = harness.load_module("metrics", "h2d_s").read
    # the span hands the arrays over; PjRt lays them out on two threads,
    # overlapping each other and the span's end; the device starts
    # 1000 ns after the last layout (the copy's flight)
    host = (_spans([("fl.h2d", 1000.0)], t0=1000.0)
            + _spans([("XlaLinearize", 3000.0)], t0=1800.0)
            + _spans([("XlaLinearize", 1000.0)], t0=4000.0)
            # a layout followed by more of the program before the device
            # starts: no flight is counted after it
            + _spans([("XlaLinearize", 500.0)], t0=8000.0)
            + _spans([("fl.launch", 100.0)], t0=8600.0))
    device = _spans([("fusion", 1000.0)], t0=6000.0) + _spans(
        [("fusion", 500.0)], t0=9000.0)
    assert read(_obs(host, device_ops=device)) == pytest.approx(
        (5000 + 500) / 2e9, rel=1e-12)
    # the runtime lays out copies whatever the program opens: without
    # the program's span the reader stays silent
    layouts = [e for e in host if e.name != "fl.h2d"]
    assert read(_obs(layouts, device_ops=device)) is None


@pytest.fixture(scope="module")
def recorded():
    tr = trace_reduce.load(str(FIXTURE))
    return harness.Observation(config={},
                               peaks=harness.peaks_for("TPU v5 lite"),
                               rounds=[{}] * ROUNDS, trace=tr,
                               window_compiles=0)


@pytest.mark.parametrize("metric, want_ns", [
    ("demux_scan_s", 3219750.0),          # 1 opening a round
    ("demux_gate_s", 4371920.0),          # 2 a round
    ("demux_stack_s", 6151031.0),         # 2 a round (f32)
    ("demux_sched_s", 4143869.0),
    ("h2d_s", 10609388.0),                # 2 a round, layouts, flights
    ("launch_s", 6993588.0),              # 2 a round
])
def test_recorded_trace_reads_fixed_numbers(recorded, metric, want_ns):
    spans = recorded.trace.host_spans(READERS[metric])
    assert len(spans) == (1 if metric in ("demux_scan_s", "demux_sched_s")
                          else 2) * ROUNDS
    got = harness.load_module("metrics", metric).read(recorded)
    assert got == pytest.approx(want_ns * 1e-9 / ROUNDS, rel=1e-12)


def test_recorded_demux_phases_sum_to_demux_s(recorded):
    read = {m: harness.load_module("metrics", m).read(recorded)
            for m in DEMUX_PHASES + ("demux_s",)}
    parts = sum(read[m] for m in DEMUX_PHASES)
    assert read["demux_s"] == pytest.approx(18150229e-9 / ROUNDS,
                                            rel=1e-12)
    assert 0.97 * read["demux_s"] <= parts <= read["demux_s"]


def test_recorded_fold_ops_carry_the_kernel_name(recorded):
    fold = harness.load_module("metrics", "fold_s")
    by_elimination = [e for e in recorded.trace.device_ops if fold.is_fold(e)]
    by_name = [e for e in recorded.trace.device_ops
               if '"kernel":"fold_f32"' in e.meta["hlo"]]
    assert len(by_name) == 128            # 32 drained batches a round
    assert by_name == by_elimination
