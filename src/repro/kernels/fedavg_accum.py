"""Pallas TPU kernel: count-normalized masked FedAvg accumulation.

The paper's worker threads walk RX rings and add each packet into a
shared float array, then one worker divides by the per-element count.  On
TPU the packet stream is laid out client-major ``(K, C, W)`` (K clients,
C chunks, W = 512-float lane-aligned packets).

The grid is **2D client-blocked** (DESIGN.md §2): ``(C // BC, K // BK)``
with the client dimension innermost, so for each chunk-block the kernel
sweeps all client-blocks while the output block stays resident in VMEM.
The f32 accumulator is carried *in the output ref* across the client
sweep: initialized when ``k_idx == 0``, accumulated on every revisit, and
divided + zero-masked on the last client-block.  Mosaic's automatic
double buffering is still the RX→worker→TX pipeline — the DMA of client
block k+1 overlaps the accumulate of block k — but VMEM per step is now
``(BK, BC, W)`` **independent of K**, so the kernel scales to thousands
of clients (K=1024, BK=8, BC=128, W=512 → 2 MiB payloads vs ~17 MB for
the old all-K layout, which exceeded the ~16 MB VMEM budget).  BC is
128 because the (BK, BC) mask block puts chunks on the lanes.

``finalize=False`` skips the divide and returns raw (sum, counts) — the
host-level streaming pipeline (core/pipeline.py) uses it to fold client
*batches* through the same kernel and divide once at END.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fedavg_accum_kernel(x_ref, m_ref, out_ref, cnt_ref, *, finalize: bool):
    """x (BK, BC, W) f32; m (BK, BC) f32 weighted-arrival mask.

    out/cnt blocks are revisited across the (innermost) client-block grid
    dimension and double as the f32 accumulator.
    """
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(x * m[:, :, None], axis=0)     # (BC, W)
    cnt_ref[...] += jnp.sum(m, axis=0)[:, None]            # (BC, 1)

    if finalize:
        @pl.when(k_idx == pl.num_programs(1) - 1)
        def _divide():
            counts = cnt_ref[...]                          # (BC, 1)
            avg = out_ref[...] / jnp.maximum(counts, 1e-12)
            out_ref[...] = jnp.where(counts > 0, avg, 0.0)


def fedavg_accum_pallas(packets: jnp.ndarray, wmask: jnp.ndarray,
                        *, block_clients: int = 8, block_chunks: int = 128,
                        finalize: bool = True,
                        interpret: bool = False):
    """packets (K, C, W) any float dtype; wmask (K, C) f32.

    Returns (avg (C, W) f32, counts (C, 1) f32); with ``finalize=False``
    the first output is the raw masked sum instead of the average.  K and
    C must be multiples of ``block_clients`` / ``block_chunks`` (ops.py
    pads both axes with mask-0 rows/chunks).
    """
    K, C, W = packets.shape
    assert K % block_clients == 0, (K, block_clients)
    # the (K, C) mask blocks put chunks on the lanes, so Mosaic needs a
    # chunk block of 128 lanes or the whole chunk axis
    block_chunks = min(block_chunks, C)
    assert C % block_chunks == 0, (C, block_chunks)
    grid = (C // block_chunks, K // block_clients)
    kernel = functools.partial(_fedavg_accum_kernel, finalize=finalize)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_clients, block_chunks, W),
                         lambda c, k: (k, c, 0)),
            pl.BlockSpec((block_clients, block_chunks),
                         lambda c, k: (k, c)),
        ],
        out_specs=[
            pl.BlockSpec((block_chunks, W), lambda c, k: (c, 0)),
            pl.BlockSpec((block_chunks, 1), lambda c, k: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, W), jnp.float32),
            jax.ShapeDtypeStruct((C, 1), jnp.float32),
        ],
        interpret=interpret,
    )(packets.astype(jnp.float32), wmask.astype(jnp.float32))
