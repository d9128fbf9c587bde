"""The plain reference agrees with the server's Pallas fold (interpret
mode) at a small size, and the control, one precision step lower,
fails the limit that sound runs pass."""
import dataclasses

import pytest

from benchutil import CELLS, harness, small_cell

import calibrate  # noqa: E402  (benchutil puts bench/ on the path)


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_pallas_round(name, monkeypatch):
    make = harness.engine_config
    monkeypatch.setattr(harness, "engine_config", lambda c: dataclasses.replace(
        make(c), scan_body="pallas"))
    line = harness.run(small_cell(name), 17, 0.2, False, require_chip=False)
    assert line["correct"], line["check"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(name):
    cell = small_cell(name)
    limit = cell.config["limits"]["fold_gap"]
    for seed in (3, 2 ** 31 + 11, 40):
        got = calibrate.readings(cell, seed, 2, require_chip=False)
        assert got["program"]["fold_gap"] <= limit
        assert got["control_fold_gap"] > limit
