"""The fold's roofline counts the algorithm's bytes from accepted
packets and shapes, whatever body runs the fold, against a peaks table
that refuses a device it does not know."""
import dataclasses

import pytest

from benchutil import CELLS, harness, small_cell


def _records(name, scan_body):
    import jax.numpy as jnp
    cell = small_cell(name)
    cfg = dataclasses.replace(harness.engine_config(cell.config),
                              scan_body=scan_body)
    g0, pool = harness.build_pool(9, cell, cfg.n_slots)
    tap = harness.SchedTap()
    tap.recording = True
    with tap.installed():
        g = jnp.asarray(g0)
        for rnd in pool:
            g = harness.serve(cfg, g, rnd).new_global
    return tap.rounds


@pytest.mark.parametrize("name", CELLS)
def test_fold_bytes_independent_of_the_fold_body(name):
    roof = harness.load_module("metrics", "fold_roofline")
    peaks = next(iter(harness.load_json(harness.PEAKS_FILE).values()))
    pallas, jnp_body = _records(name, "pallas"), _records(name, "jnp")
    assert [roof.least_seconds(r, peaks) for r in pallas] == \
        [roof.least_seconds(r, peaks) for r in jnp_body]
    cell = small_cell(name)
    r = pallas[0]
    w, k = cell.config["payload"], r["wire_itemsize"]
    q8 = cell.config["wire"] == "q8"
    assert r["q8"] == q8 and k == (1 if q8 else 4)
    want = (r["accepted"] * (w * k + 8 + 4 * q8)
            + 2 * r["n_slots"] * (w + 1) * 4)
    assert roof.fold_bytes(r["accepted"], w, k, q8, r["n_slots"]) == want


def test_bytes_bound_the_fold():
    roof = harness.load_module("metrics", "fold_roofline")
    peaks = harness.peaks_for("TPU v5 lite")
    rnd = dict(accepted=119_000, payload=367, wire_itemsize=4, q8=False,
               n_slots=12_495)
    least = roof.least_seconds(rnd, peaks)
    assert least == roof.fold_bytes(119_000, 367, 4, False, 12_495) \
        / peaks["hbm_bytes_per_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
