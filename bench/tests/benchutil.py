"""Helpers the benchmark's CPU tests share: a cell cut to a size the
CPU runs in seconds, and the harness on the import path."""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CELLS = ["paper-cnn-k10-f32.lossy", "xdev-cnn-k100-q8.stragglers"]
# a few tens of slots per client and few clients: the CPU rehearses the
# path and the arithmetic; speed means nothing here
SMALL = {"paper-cnn-k10-f32.lossy": dict(n_params=367 * 40 - 11,
                                         n_clients=6),
         "xdev-cnn-k100-q8.stragglers": dict(n_params=1464 * 24 - 5,
                                             n_clients=12)}


def small_cell(name: str, **extra):
    cell = harness.load_cell(name, overrides=dict(SMALL[name], **extra))
    return cell


def cpu_peaks(tmp_path) -> Path:
    """A peaks table that knows the CPU, for driving the traced path
    here (the real table knows chips only)."""
    table = json.loads(harness.PEAKS_FILE.read_text())
    p = Path(tmp_path) / "peaks.json"
    p.write_text(json.dumps({"cpu": next(iter(table.values()))}))
    return p
