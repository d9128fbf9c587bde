"""Pallas TPU kernel: int8 dequantizing FedAvg accumulation (beyond paper).

Consumes the int8 wire format of the compressed aggregation path
(core/distributed.py 'int8' mode): per-chunk absmax-scaled int8 payloads.
Dequantization fuses into the accumulate, so the f32 copies of the client
payloads never materialize in HBM — HBM traffic drops ~4x vs the f32
kernel, which matters because the aggregation is memory-bound (roofline:
~0.25 flop/byte).

Same 2D client-blocked grid / accumulator-revisit structure as
fedavg_accum.py (DESIGN.md §2): the output block is the f32 accumulator
carried across the innermost client-block sweep, so VMEM per step is
``(BK, BC, W)`` int8 + the f32 output block, independent of K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantized_accum_kernel(q_ref, s_ref, m_ref, out_ref, cnt_ref,
                            *, finalize: bool):
    """q (BK, BC, W) int8; s (BK, BC) f32 scales; m (BK, BC) f32 mask."""
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(q * (s * m)[:, :, None], axis=0)   # dequant*mask
    cnt_ref[...] += jnp.sum(m, axis=0)[:, None]

    if finalize:
        @pl.when(k_idx == pl.num_programs(1) - 1)
        def _divide():
            counts = cnt_ref[...]
            avg = out_ref[...] / jnp.maximum(counts, 1e-12)
            out_ref[...] = jnp.where(counts > 0, avg, 0.0)


def quantized_accum_pallas(q: jnp.ndarray, scales: jnp.ndarray,
                           wmask: jnp.ndarray, *, block_clients: int = 8,
                           block_chunks: int = 128, finalize: bool = True,
                           interpret: bool = False):
    """q (K, C, W) int8; scales, wmask (K, C) f32 -> (avg (C,W), counts (C,1))."""
    K, C, W = q.shape
    assert K % block_clients == 0, (K, block_clients)
    # the (K, C) mask blocks put chunks on the lanes, so Mosaic needs a
    # chunk block of 128 lanes or the whole chunk axis
    block_chunks = min(block_chunks, C)
    assert C % block_chunks == 0, (C, block_chunks)
    grid = (C // block_chunks, K // block_clients)
    kernel = functools.partial(_quantized_accum_kernel, finalize=finalize)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_clients, block_chunks, W),
                         lambda c, k: (k, c, 0)),
            pl.BlockSpec((block_clients, block_chunks),
                         lambda c, k: (k, c)),
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
    )(q, scales.astype(jnp.float32), wmask.astype(jnp.float32))
