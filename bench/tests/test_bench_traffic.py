"""The traffic generator: deterministic per seed, and its ``truth`` is
what the server's demux accepts."""
import numpy as np
import pytest

from benchutil import CELLS, harness, small_cell


def _pool(name, seed):
    cell = small_cell(name)
    cfg = harness.engine_config(cell.config)
    return cell, cfg, harness.build_pool(seed, cell, cfg.n_slots)


def _signature(events):
    return [(p.kind.name, p.client, p.index, p.scale,
             None if x is None else np.asarray(x).tobytes())
            for p, x in events]


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_traffic(name):
    _, _, (g_a, pool_a) = _pool(name, 2 ** 31 + 7)
    _, _, (g_b, pool_b) = _pool(name, 2 ** 31 + 7)
    assert np.array_equal(g_a, g_b)
    for a, b in zip(pool_a, pool_b):
        assert _signature(a.events) == _signature(b.events)
        assert np.array_equal(a.accepted, b.accepted)
        assert (a.duplicates, a.stragglers) == (b.duplicates, b.stragglers)


@pytest.mark.parametrize("name", CELLS)
def test_other_seed_other_traffic(name):
    _, _, (_, pool_a) = _pool(name, 1)
    _, _, (_, pool_b) = _pool(name, 2)
    assert _signature(pool_a[0].events) != _signature(pool_b[0].events)


@pytest.mark.parametrize("name", CELLS)
def test_truth_is_what_demux_accepts(name):
    from repro.core.engine_compiled import demux_events
    cell, cfg, (_, pool) = _pool(name, 11)
    for rnd in pool:
        _, stats, up = demux_events(cfg, rnd.events)
        assert np.array_equal(up > 0, rnd.accepted)
        assert stats.data_enqueued == rnd.accepted.sum()
        assert stats.duplicates_dropped == rnd.duplicates
        want = rnd.stragglers if cell.config["round_close"] == "finalize" \
            else 0
        assert stats.stragglers_timed_out == want


def test_mix_shapes_the_round():
    """Bursts leave slots that reach the server from nobody; stragglers
    send no END and a prefix of their packets."""
    cell, cfg, (_, pool) = _pool("xdev-cnn-k100-q8.stragglers", 5)
    mix = cell.mix
    k = cell.config["n_clients"]
    for rnd in pool:
        assert (rnd.accepted.sum(axis=0) == 0).sum() >= mix["burst_len"]
        ends = {p.client for p, _ in rnd.events if p.kind.name == "END"}
        assert rnd.stragglers == round(mix["straggle_rate"] * k)
        assert len(ends) == k - rnd.stragglers
        short = [c for c in range(k) if c not in ends]
        full = [c for c in ends]
        assert rnd.accepted[short].sum(axis=1).max() < \
            rnd.accepted[full].sum(axis=1).min()
