"""A run with the timed path broken underneath must come out not
``correct``: the harness is driven whole, with the chip check skipped,
once for each fault a single-chip round can have."""
import contextlib
import pytest

from benchutil import CELLS, harness, small_cell


@contextlib.contextmanager
def broken(fault: str):
    import jax.numpy as jnp
    from repro.core import engine_compiled as ec
    orig_dispatch, orig_demux = ec.dispatch_round, ec.demux_events

    def dispatch_round(cfg, sched, total, counts, prev_global, **kw):
        total, counts, new_global, flats = orig_dispatch(
            cfg, sched, total, counts, prev_global, **kw)
        if fault == "state_unchanged":
            new_global = jnp.asarray(prev_global)
        elif fault == "answer_altered":
            new_global = new_global.at[new_global.size // 2].add(1.0)
        return total, counts, new_global, flats

    def demux_events(cfg, events, weights=None):
        sched, stats, up = orig_demux(cfg, events, weights)
        if fault == "half_batch":
            # every other packet of each drained batch never reaches the
            # fold; the divide then takes the mean over the rest
            sched.idx[:, 1::2] = -1
            sched.weights[:, 1::2] = 0.0
        return sched, stats, up

    ec.dispatch_round, ec.demux_events = dispatch_round, demux_events
    try:
        yield
    finally:
        ec.dispatch_round, ec.demux_events = orig_dispatch, orig_demux


def _run(name):
    cell = small_cell(name)
    return harness.run(cell, 2 ** 31 + 3, 0.5, False, require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = _run(name)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_caught(name, fault):
    with broken(fault):
        line = _run(name)
    assert not line["correct"]
    assert line["failed"] == line["attempted"] > 0
