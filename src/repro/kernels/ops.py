"""jit'd public wrappers for the Pallas kernels.

On non-TPU backends the kernels run in ``interpret=True`` mode (the
kernel body executes as traced jnp on CPU), which is how this container
validates them; on TPU they compile through Mosaic.  Wrappers pad both
the client axis and the chunk axis up to block multiples and strip the
chunk padding off again.  All padding is zero-fill (``jnp.pad`` with
``constant_values=0``), so padded clients/chunks carry a zero mask and
contribute neither to the sums nor to the counts — counts stay exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fedavg_accum import fedavg_accum_pallas
from repro.kernels.packet_scatter import (BLOCK_PKTS,
                                          packet_scatter_accum_pallas,
                                          packet_scatter_pallas)
from repro.kernels.quantized_accum import quantized_accum_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(arrs, size: int, block: int, axis: int):
    """Zero-pad ``axis`` of each array up to a multiple of ``block``.

    Zero-fill means the (K, C) masks are 0 in every padded row/chunk, so
    padded entries are inert in both the accumulate and the count.
    """
    pad = (-size) % block
    if pad == 0:
        return arrs
    out = []
    for a in arrs:
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        out.append(jnp.pad(a, widths, constant_values=0))
    return out


@functools.partial(jax.jit,
                   static_argnames=("block_clients", "block_chunks",
                                    "finalize"))
def fedavg_accum(packets, wmask, block_clients: int = 8,
                 block_chunks: int = 128, finalize: bool = True):
    """(K, C, W) payloads + (K, C) weighted mask -> (avg (C, W), counts (C,)).

    With ``finalize=False`` the first output is the raw masked sum
    (streaming partial aggregation — divide happens at END).
    """
    K, C, W = packets.shape
    packets, wmask = _pad_axis([packets, wmask], K, block_clients, 0)
    packets, wmask = _pad_axis([packets, wmask], C, block_chunks, 1)
    avg, cnt = fedavg_accum_pallas(packets, wmask,
                                   block_clients=block_clients,
                                   block_chunks=block_chunks,
                                   finalize=finalize,
                                   interpret=_interpret())
    return avg[:C], cnt[:C, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_clients", "block_chunks",
                                    "finalize"))
def quantized_accum(q, scales, wmask, block_clients: int = 8,
                    block_chunks: int = 128, finalize: bool = True):
    """int8 (K, C, W) + scales/mask (K, C) -> (avg (C, W), counts (C,))."""
    K, C, W = q.shape
    q, scales, wmask = _pad_axis([q, scales, wmask], K, block_clients, 0)
    q, scales, wmask = _pad_axis([q, scales, wmask], C, block_chunks, 1)
    avg, cnt = quantized_accum_pallas(q, scales, wmask,
                                      block_clients=block_clients,
                                      block_chunks=block_chunks,
                                      finalize=finalize,
                                      interpret=_interpret())
    return avg[:C], cnt[:C, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_clients", "block_chunks"),
                   donate_argnums=(0, 1))
def fedavg_accum_into(total, counts, packets, wmask,
                      block_clients: int = 8, block_chunks: int = 128):
    """Streaming fold: (total (C, W), counts (C,)) += raw masked sums.

    The accumulator pair is *donated* (``donate_argnums``), so the
    caller's buffers are reused in place and the streaming hot path
    (``StreamingAggregator.add_batch``) stops allocating a fresh (C, W)
    total per drained batch.  The caller must drop its references after
    the call — on backends with donation support the inputs are deleted.
    """
    sums, cnts = fedavg_accum(packets, wmask, block_clients=block_clients,
                              block_chunks=block_chunks, finalize=False)
    return total + sums, counts + cnts


@functools.partial(jax.jit, static_argnames=("n_slots",))
def packet_scatter(packets, idx, n_slots: int, init=None):
    """Place packets (N, W) at rows idx (N,) of a (n_slots, W) buffer.

    ``init`` (default zeros) is aliased onto the output: uncovered rows
    keep its contents; duplicated idx resolve last-writer-wins.
    """
    return packet_scatter_pallas(packets, idx, n_slots, init=init,
                                 interpret=_interpret())


def _packet_scatter_accum_impl(packets, idx, acc, counts, weights,
                               mode: str, block_slots: int,
                               block_pkts: int):
    N, W = packets.shape
    S = counts.shape[0]
    # pad the batch axis with idx=-1 (matches no slot) / weight 0
    pad_n = (-N) % block_pkts
    if pad_n:
        packets = jnp.pad(packets, ((0, pad_n), (0, 0)))
        idx = jnp.pad(idx.astype(jnp.int32), (0, pad_n), constant_values=-1)
        weights = jnp.pad(weights, (0, pad_n))
    acc2, cnt2 = _pad_axis([acc, counts[:, None]], S, block_slots, 0)
    acc_out, cnt_out = packet_scatter_accum_pallas(
        packets, idx, weights, acc2, cnt2, exact=(mode == "exact"),
        block_slots=block_slots, block_pkts=block_pkts,
        interpret=_interpret())
    return acc_out[:S], cnt_out[:S, 0]


_packet_scatter_accum = jax.jit(
    _packet_scatter_accum_impl,
    static_argnames=("mode", "block_slots", "block_pkts"))
# donating variant: acc/counts buffers are reused in place, so the
# per-drain hot path (StreamingAggregator.scatter_add, the compiled
# round engine) stops allocating a fresh (S, W) total per call
_packet_scatter_accum_donated = jax.jit(
    _packet_scatter_accum_impl,
    static_argnames=("mode", "block_slots", "block_pkts"),
    donate_argnums=(2, 3))


def packet_scatter_accum(packets, idx, acc, counts, weights=None,
                         mode: str = "exact", block_slots: int = 8,
                         block_pkts: int = BLOCK_PKTS,
                         donate: bool = False):
    """Scatter-accumulate a drained ring batch into live (acc, counts).

    packets (N, W) at slot rows idx (N,) int32; acc (S, W) f32; counts
    (S,) f32; weights (N,) optional per-arrival FedAvg weights.  Returns
    (acc', counts').  ``mode="exact"`` adds every arrival; ``"approx"``
    is the deterministic lock-free race: within this batch the last
    writer to a slot wins against the call-entry snapshot, while counts
    still see every arrival (DESIGN.md §3).  Ring padding is expressed
    as idx=-1 / weight=0 and is inert in both sums and counts.

    ``donate=True`` donates the (acc, counts) buffers to the call
    (``jax.jit(..., donate_argnums)``): the accumulator is updated in
    place instead of reallocated per drain.  Callers must treat the
    passed arrays as consumed.
    """
    if mode not in ("exact", "approx"):
        raise ValueError(mode)
    if weights is None:
        weights = jnp.ones((packets.shape[0],), jnp.float32)
    fn = _packet_scatter_accum_donated if donate else _packet_scatter_accum
    return fn(packets, idx, acc, counts, weights, mode=mode,
              block_slots=block_slots, block_pkts=block_pkts)
