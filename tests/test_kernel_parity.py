"""Direct parity pins for the public Pallas kernels (DESIGN.md §13).

The staticcheck `parity` rule requires every public ``*_pallas`` entry
point to be referenced by name from a test that pins it against its
pure-jnp twin.  The engine/robust suites exercise
``packet_scatter_accum_pallas`` and ``robust_finalize_pallas`` through
their wrappers; this file covers the remaining kernels *directly*, at
their own signatures, in interpret mode on CPU.

All payloads are integer-valued and the q8 scales are powers of two, so
every product and partial sum is exactly representable in f32: the
kernel's blocked accumulation order and the twin's one-shot einsum/dot
must then agree **bitwise**, for any block tiling.  The fold's hit-block
grid is pinned on non-integer payloads instead, with the weights and
duplicates chosen so that no sum depends on its order.
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packet_scatter as ps
from repro.kernels.fedavg_accum import fedavg_accum_pallas
from repro.kernels.packet_scatter import (BLOCK_PKTS,
                                          packet_scatter_accum_batch_jnp,
                                          packet_scatter_accum_batch_q8_jnp,
                                          packet_scatter_accum_pallas,
                                          packet_scatter_accum_q8_pallas,
                                          packet_scatter_pallas)
from repro.kernels.quantized_accum import quantized_accum_pallas
from repro.kernels.ref import (fedavg_accum_ref, packet_scatter_ref,
                               quantized_accum_ref)

K, C, W = 16, 8, 8      # clients, chunks, payload width (block multiples)


def _masked_payloads(seed):
    rng = np.random.default_rng(seed)
    pk = rng.integers(-8, 8, (K, C, W)).astype(np.float32)
    m = (rng.random((K, C)) < 0.7).astype(np.float32)
    return jnp.asarray(pk), jnp.asarray(m)


@pytest.mark.parametrize("finalize", [True, False])
def test_fedavg_accum_pallas_matches_ref(finalize):
    pk, m = _masked_payloads(0)
    avg, cnt = fedavg_accum_pallas(pk, m, finalize=finalize, interpret=True)
    ravg, rcnt = fedavg_accum_ref(pk, m, finalize=finalize)
    np.testing.assert_array_equal(np.asarray(avg), np.asarray(ravg))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


@pytest.mark.parametrize("finalize", [True, False])
def test_quantized_accum_pallas_matches_ref(finalize):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.integers(-127, 128, (K, C, W)).astype(np.int8))
    scales = jnp.asarray(
        (2.0 ** rng.integers(-3, 4, (K, C))).astype(np.float32))
    m = jnp.asarray((rng.random((K, C)) < 0.6).astype(np.float32))
    avg, cnt = quantized_accum_pallas(q, scales, m, finalize=finalize,
                                      interpret=True)
    ravg, rcnt = quantized_accum_ref(q, scales, m, finalize=finalize)
    np.testing.assert_array_equal(np.asarray(avg), np.asarray(ravg))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


def test_packet_scatter_pallas_matches_ref():
    rng = np.random.default_rng(2)
    n_pkts, n_slots = 24, 16
    pk = jnp.asarray(rng.integers(-50, 50, (n_pkts, W)).astype(np.float32))
    # duplicates on purpose: placement must be last-writer-wins
    idx = jnp.asarray(rng.integers(0, n_slots, n_pkts).astype(np.int32))
    init = jnp.asarray(rng.integers(-5, 5, (n_slots, W)).astype(np.float32))
    got = packet_scatter_pallas(pk, idx, n_slots, init=init, interpret=True)
    want = packet_scatter_ref(pk, idx, n_slots, init=init)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("exact", [True, False])
def test_packet_scatter_accum_q8_pallas_matches_jnp_twin(exact):
    rng = np.random.default_rng(3)
    n_pkts, n_slots = 2 * BLOCK_PKTS, 16
    q = rng.integers(-127, 128, (n_pkts, W)).astype(np.int8)
    scales = (2.0 ** rng.integers(-3, 4, n_pkts)).astype(np.float32)
    idx = rng.integers(0, n_slots, n_pkts).astype(np.int32)
    weights = rng.integers(0, 3, n_pkts).astype(np.float32)
    # ring padding: inert entries carry idx -1, weight 0, scale 0
    idx[-3:], weights[-3:], scales[-3:] = -1, 0.0, 0.0
    acc = rng.integers(-4, 4, (n_slots, W)).astype(np.float32)
    cnt = rng.integers(0, 4, (n_slots, 1)).astype(np.float32)
    args = tuple(jnp.asarray(a) for a in (q, scales, idx, weights, acc, cnt))
    ga, gc = packet_scatter_accum_q8_pallas(*args, exact=exact,
                                            interpret=True)
    wa, wc = packet_scatter_accum_batch_q8_jnp(*args, exact=exact)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))


# --- the fold's hit-block grid (DESIGN.md §3) -------------------------
# Non-integer payloads here: weights are 0 or powers of two and no slot
# takes more than two packets of one packet block, so every product is
# exact and every per-slot sum has one rounding in any order — the
# kernel, its jnp twin and the dense sweep must then agree bitwise.  The
# kernels run in the TPU interpreter, which models the block pipeline
# and fills VMEM it has not loaded with NaN: a visited block written back
# without its first-visit load shows.
TPU_INTERPRET = pltpu.InterpretParams()

# 32-packet blocks keep the interpreted grids short; 40 slot blocks are
# more than one batch of 32 can hit, so the grid skips blocks
BP, S_FOLD, W_FOLD = 32, 320, 16


def _fold_case(case: str, rng):
    """(idx (N,), n_slots) of one batch shape the grid must handle."""
    n, s = BP, S_FOLD
    idx = np.full(n, -1, np.int32)
    if case == "all_inert":
        pass
    elif case == "one_block":       # 8 slots of block 9, each hit twice
        idx[:16] = np.tile(np.arange(72, 80), 2)
    elif case == "dup_slot":
        idx[:20] = rng.choice(s, 20, replace=False)
        idx[20] = idx[7]
    elif case == "interleaved":     # padding between real packets
        idx[::2] = rng.choice(s, n // 2, replace=False)
    elif case == "small_acc":       # 2 blocks < 32 packets: all blocks
        s = 16
        idx[:30] = np.concatenate([rng.permutation(s),
                                   rng.permutation(s)])[:30]
    elif case == "two_pkt_blocks":  # blocks shared, slots not, across j
        n = 2 * BP
        idx = np.full(n, -1, np.int32)
        idx[:20] = 2 * rng.choice(s // 2, 20, replace=False)
        idx[BP:BP + 20] = 2 * rng.choice(s // 2, 20, replace=False) + 1
    elif case == "table":           # S·K rows: 33 slots × 10 clients
        s = 336
        idx[:16] = rng.choice(330, 16, replace=False)
    else:
        raise ValueError(case)
    return idx, s


@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("wire", ["f32", "q8"])
@pytest.mark.parametrize("case", ["all_inert", "one_block", "dup_slot",
                                  "interleaved", "small_acc",
                                  "two_pkt_blocks", "table"])
def test_fold_hit_block_grid_matches_twin_and_dense_sweep(case, wire,
                                                          exact):
    rng = np.random.default_rng(
        zlib.crc32(f"{case}/{wire}/{exact}".encode()))
    idx, s = _fold_case(case, rng)
    n = idx.shape[0]
    w = rng.choice(np.float32([0.0, 0.5, 1.0, 2.0]), n)
    w[idx < 0] = 0.0
    acc = jnp.asarray(rng.standard_normal((s, W_FOLD), np.float32))
    cnt = jnp.asarray(rng.random((s, 1), np.float32))
    idx, w = jnp.asarray(idx), jnp.asarray(w)
    kw = dict(exact=exact, block_pkts=BP)
    if wire == "f32":
        pk = jnp.asarray(rng.standard_normal((n, W_FOLD), np.float32))
        got = packet_scatter_accum_pallas(pk, idx, w, acc, cnt,
                                          interpret=TPU_INTERPRET, **kw)
        twin = packet_scatter_accum_batch_jnp(pk, idx, w, acc, cnt,
                                              exact=exact)
    else:
        pk = jnp.asarray(rng.integers(-127, 128, (n, W_FOLD), np.int8))
        sc = jnp.asarray(rng.random(n, np.float32) * 0.05)
        got = packet_scatter_accum_q8_pallas(pk, sc, idx, w, acc, cnt,
                                             interpret=TPU_INTERPRET, **kw)
        twin = packet_scatter_accum_batch_q8_jnp(pk, sc, idx, w, acc, cnt,
                                                 exact=exact)
    # the dense sweep: the same kernel body over every block in order
    # (the reference, so the plain interpreter will do)
    n_blocks = s // 8
    every = (jnp.arange(n_blocks, dtype=jnp.int32), jnp.int32(n_blocks))
    grid = dict(block_slots=8, interpret=True, **kw)
    if wire == "f32":
        dense = ps._fold_f32(pk, idx, w, acc, cnt, every, **grid)
    else:
        dense = ps._fold_q8(pk, sc, idx, w, acc, cnt, every, **grid)
    for want in (twin, dense):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
    if case == "all_inert":
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(acc))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(cnt))


def test_fold_block_visits_counts_the_grid_steps_that_accumulate(
        monkeypatch):
    """On a drain schedule in the paper cell's ring layout (5 rings of
    64, round-robin, 128-packet rows) at a small S, the counter's
    ``visited`` is the number of grid steps whose body accumulates, as
    the interpreted kernel takes them, and ``dense`` is every block of
    every row."""
    import jax

    from _drain_reference import expand
    from repro.core.engine_compiled import build_drain_schedule

    rng = np.random.default_rng(16)
    s, k, w = 1_088, 2, 8
    slots = np.tile(np.arange(s, dtype=np.int32), k)
    slots = slots[rng.random(slots.size) > 0.05]
    rng.shuffle(slots)
    sched = build_drain_schedule(
        slots, np.ones(slots.size, np.float32),
        rng.standard_normal((slots.size, w), np.float32),
        n_workers=5, ring_capacity=64, ring_assign="rr")
    steps = []
    body = ps._scatter_accum_body

    def counting_body(blk_ref, nhit_ref, idx_ref, w_ref, load_pkt, *a,
                      **kw):
        def load():         # called only by the steps that accumulate
            jax.debug.callback(lambda: steps.append(1))
            return load_pkt()
        return body(blk_ref, nhit_ref, idx_ref, w_ref, load, *a, **kw)

    monkeypatch.setattr(ps, "_scatter_accum_body", counting_body)
    acc, _ = ps.packet_scatter_accum_scan(
        jnp.asarray(sched.idx), jnp.asarray(sched.weights),
        jnp.asarray(expand(sched.payloads, sched.src)), jnp.zeros((s, w)),
        jnp.zeros((s, 1)), use_pallas=True, interpret=True)
    acc.block_until_ready()
    jax.effects_barrier()
    visited, dense = ps.fold_block_visits(sched.idx, s)
    assert sched.idx.shape[1] == BLOCK_PKTS
    assert visited == len(steps) > 0
    assert dense == sched.idx.shape[0] * s // 8
    assert visited < dense
