"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

Interpret mode on the CPU runs a kernel's body as jnp and cannot see
what Mosaic refuses: block shapes off the (8, 128) tiling, slices it
does not lower, more VMEM than a kernel may use.  These tests compile
each kernel at the widths the server runs — the paper CNN's update in
367-f32 or 1464-int8 packets — for a ``v5e:2x2`` topology that is
described, not attached, and check that the executable holds the
kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine_compiled as ec
from repro.kernels.fedavg_accum import fedavg_accum_pallas
from repro.kernels.packet_scatter import (packet_scatter_accum_pallas,
                                          packet_scatter_accum_q8_pallas,
                                          packet_scatter_accum_scan,
                                          packet_scatter_pallas,
                                          robust_finalize_pallas)
from repro.kernels.quantized_accum import quantized_accum_pallas

# the paper CNN's update: 4,585,546 f32 parameters
S_F32 = 12_496        # 12,495 slots of 367 f32, padded to 8-slot blocks
S_Q8 = 3_136          # 3,133 slots of 1464 int8, padded to 8-slot blocks
W_F32, W_Q8 = 367, 1464
B = 64                # one drained ring of the default capacity


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("exact", [True, False])
def test_scatter_accum_f32_compiles(spec, exact):
    _compile(lambda p, i, w, a, c: packet_scatter_accum_pallas(
        p, i, w, a, c, exact=exact, block_pkts=B),
        spec((B, W_F32)), spec((B,), jnp.int32), spec((B,)),
        spec((S_F32, W_F32)), spec((S_F32, 1)))


def test_scatter_accum_q8_compiles(spec):
    _compile(lambda p, s, i, w, a, c: packet_scatter_accum_q8_pallas(
        p, s, i, w, a, c, block_pkts=B),
        spec((B, W_Q8), jnp.int8), spec((B,)), spec((B,), jnp.int32),
        spec((B,)), spec((S_Q8, W_Q8)), spec((S_Q8, 1)))


def test_pallas_scan_compiles(spec):
    """The fold as the engine dispatches it: drained rings of 64 padded
    to the 128-packet block, scanned with the accumulator carried."""
    rows, b = 8, 128
    _compile(lambda i, w, p, a, c: packet_scatter_accum_scan(
        i, w, p, a, c, use_pallas=True),
        spec((rows, b), jnp.int32), spec((rows, b)),
        spec((rows, b, W_F32)), spec((S_F32, W_F32)), spec((S_F32, 1)))


@pytest.mark.parametrize("q8", [False, True])
def test_single_chip_round_compiles(spec, q8):
    """The whole round ``lower_round`` gives a single-chip schedule on
    the chip: the shipped rows (a ring of 64 per drained batch) are
    gathered into the 128-packet blocks on the device and folded by
    the Pallas kernel."""
    rows, b, s = 8, 128, 64       # the real widths; few slots, to compile fast
    w = W_Q8 if q8 else W_F32
    text = ec._round_device.lower(
        spec((s, w)), spec((s,)), spec((rows, b), jnp.int32),
        spec((rows, b)), spec((rows * B, w), jnp.int8 if q8 else jnp.float32),
        spec((rows, b), jnp.int32), spec((rows, b)) if q8 else None,
        spec((s * w,)), None, None, mode="exact", payload=w,
        n_params=s * w, use_pallas=True, block_slots=8, block_pkts=b,
        mix_alpha=0.0, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text and "gather" in text


@pytest.mark.parametrize("k", [64, 256])
def test_robust_finalize_compiles(spec, k):
    _compile(lambda t, p: robust_finalize_pallas(t, p),
             spec((S_F32, k, W_F32)), spec((S_F32, k)))


def test_packet_scatter_compiles(spec):
    n = 1024
    _compile(lambda p, i: packet_scatter_pallas(p, i, S_F32),
             spec((n, W_F32)), spec((n,), jnp.int32))


def test_fedavg_accum_compiles(spec):
    k, c, w = 16, 12_544, 384     # ops.py pads chunks and lanes
    _compile(fedavg_accum_pallas, spec((k, c, w)), spec((k, c)))


def test_quantized_accum_compiles(spec):
    k, c, w = 16, 12_544, 384
    _compile(quantized_accum_pallas, spec((k, c, w), jnp.int8),
             spec((k, c)), spec((k, c)))


def test_robust_round_compiles_at_the_trimmed_cell(spec):
    """The trimmed-mean round as the benchmark's paper cell serves it on
    one chip: 5,450 slots of 367 f32, K = 10, 816 drained rows shipped
    as rings of 64 and gathered into 128-packet blocks; the table fold
    and the named rank-select finalize both lower to Pallas kernels."""
    s, k, rows, b = 5_450, 10, 816, 128
    text = ec._robust_round_device.lower(
        spec((rows, b), jnp.int32), spec((rows, b)),
        spec((rows * B, W_F32)), spec((rows, b), jnp.int32), None,
        spec((s * W_F32,)), None, None, payload=W_F32,
        n_params=s * W_F32, n_slots=s, n_clients=k, use_pallas=True,
        block_slots=8, block_pkts=b, mix_alpha=0.0, interpret=False,
        median=False, beta=0.1).compile().as_text()
    assert "tpu_custom_call" in text
    assert '"kernel":"robust_finalize"' in text
    assert '"kernel":"fold_f32"' in text


# the fold's hit-block grid at the benchmark cells' accumulators, with a
# ring of 64 padded to the 128-packet block (DESIGN.md §3)
B_CELL = 128


def _compile_named(name, fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f'"kernel":"{name}"' in text


@pytest.mark.parametrize("s", [5_456, 54_504],
                         ids=["paper_accumulator", "trimmed_table"])
def test_hit_block_fold_f32_compiles(spec, s):
    _compile_named("fold_f32", lambda p, i, w, a, c:
                   packet_scatter_accum_pallas(p, i, w, a, c),
                   spec((B_CELL, W_F32)), spec((B_CELL,), jnp.int32),
                   spec((B_CELL,)), spec((s, W_F32)), spec((s, 1)))


def test_hit_block_fold_q8_compiles(spec):
    s = 1_368                     # the xdev cell's 1,367 slots, padded
    _compile_named("fold_q8", lambda p, sc, i, w, a, c:
                   packet_scatter_accum_q8_pallas(p, sc, i, w, a, c),
                   spec((B_CELL, W_Q8), jnp.int8), spec((B_CELL,)),
                   spec((B_CELL,), jnp.int32), spec((B_CELL,)),
                   spec((s, W_Q8)), spec((s, 1)))


def test_pallas_scan_lists_hit_blocks_before_the_scan(spec):
    """The scan's hit-block lists come from one sort of the whole
    schedule ahead of the loop, none inside its body, and the loop's
    body is the named kernel."""
    rows, s = 16, 5_456
    args = (spec((rows, B_CELL), jnp.int32), spec((rows, B_CELL)),
            spec((rows, B_CELL, W_F32)), spec((s, W_F32)), spec((s, 1)))

    def fold(i, w, p, a, c):
        return packet_scatter_accum_scan(i, w, p, a, c, use_pallas=True)
    jaxpr = jax.make_jaxpr(fold)(*args).jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert "sort" in str(jaxpr)
    assert "sort" not in str(scan.params["jaxpr"])
    _compile_named("fold_f32", fold, *args)
