"""chip_smoke.py's phases and references, rehearsed on the CPU.

The script runs on a TPU at the paper CNN's full update; here each
phase runs at a small CNN through the Pallas kernels in interpret mode
(``scan_body="pallas"``), so its references and bitwise checks are
exercised on every change.  The checks that the round lowers to a
Mosaic kernel need the chip and are off (``on_chip=False``).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.paper_cnn import CNNConfig  # noqa: E402

SMALL_CNN = CNNConfig(conv_channels=(8, 8, 8, 8), fc_hidden=16)


@pytest.fixture(scope="module")
def smoke():
    return chip_smoke.Smoke(seed=0, cnn=SMALL_CNN, on_chip=False,
                            scan_body="pallas")


def test_exact_then_approx_phases(smoke, capsys):
    ev, exact_global = chip_smoke.phase_a(smoke)
    chip_smoke.phase_b(smoke, ev, exact_global)
    out = capsys.readouterr().out
    assert '"int_bitwise": true' in out and '"twin_bitwise": true' in out


@pytest.mark.parametrize("phase", ["phase_c", "phase_d"])
def test_q8_and_trimmed_mean_phases(smoke, phase, capsys):
    getattr(chip_smoke, phase)(smoke)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    # a config this process has not run yet compiles; the tests never
    # turn the persistent cache on, so nothing is loaded from it
    assert line["compile_s"] > 0 and line["cache_hits"] == 0


def test_integer_mean_reference_is_exact(smoke):
    """Phase a's bitwise claim rests on this: every per-slot mean of
    K <= 10 payloads that are multiples of lcm(1..10) is an integer."""
    import numpy as np
    pk = chip_smoke.packetize_np(smoke.ints, 367)
    up = np.random.default_rng(1).random(pk.shape[:2]) < 0.9
    want, counts = chip_smoke.mean_reference(pk, up, smoke.prev_int,
                                             smoke.n_params)
    assert np.array_equal(want, np.round(want))
    assert counts.max() <= smoke.k


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_chip_or_repo(tmp_path, alone):
    """No TPU, or a directory holding the script and nothing else of
    the repo: a nonzero exit and no result line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = tmp_path / "chip_smoke.py"
        script.write_text((ROOT / "chip_smoke.py").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=script.parent, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_sharded_phases_on_four_cpu_devices():
    """--chips 4's rounds on four virtual CPU devices (a fresh process:
    XLA_FLAGS is read when JAX starts)."""
    prog = (
        "import chip_smoke\n"
        "from repro.configs.paper_cnn import CNNConfig\n"
        "s = chip_smoke.Smoke(seed=0, on_chip=False, scan_body='pallas',\n"
        "    cnn=CNNConfig(conv_channels=(8, 8, 8, 8), fc_hidden=16))\n"
        "chip_smoke.phases_sharded(s)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count('"bitwise_vs_shards_1": true') == 2
