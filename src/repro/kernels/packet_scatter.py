"""Pallas TPU kernels for the packet path: placement and scatter-accumulate.

UDP packets arrive out of order; the paper prefixes each payload with a
4-byte index so the server can place it at the right offset of the flat
parameter buffer (§4.1).  Two kernels cover the two server designs:

``packet_scatter_pallas``
    Pure *placement*: scalar-prefetched destination indices make the
    output BlockSpec of each grid step data-dependent, so packet block i
    DMAs straight to row ``idx[i]`` of the output — placement happens in
    the DMA engine, no gather/scatter HLO.  The destination buffer is
    passed in and aliased onto the output, so rows no packet covers keep
    their previous contents (the paper's server reuses the parameter
    buffer across rounds) and duplicated indices resolve last-writer-wins
    in grid order.

``packet_scatter_accum_pallas``
    The worker loop (§3.2.2): a drained ring batch of packets is *added*
    into a live ``(n_slots, W)`` accumulator with per-slot arrival
    counts.  The grid is (slot-block, packet-block) with the packet sweep
    innermost; the accumulator block is revisited across the sweep and
    carries the running sum in VMEM (DESIGN.md §3).  Packets are routed
    by a one-hot (slot × packet) matrix multiply, so the scatter runs on
    the MXU instead of serializing per-packet stores.  Two modes:

    - ``exact``  : every arrival adds (duplicates add twice) — the
      paper's server *with* exclusive access control.
    - ``approx`` : the lock-free race, made deterministic: every writer
      reads the accumulator snapshot taken at call entry, and when
      several packets in the batch hit the same slot only the last
      write survives (last-writer-wins); counts still see every
      arrival, reproducing the lost-update bias of §3.2/§4.

Both kernels run under ``interpret=True`` on CPU (how CI validates
them); on TPU they compile through Mosaic.

Two scan-level entry points extend the accumulate kernel to whole
rounds (DESIGN.md §3): ``packet_scatter_accum_batch_jnp`` is the
bitwise jnp twin of one kernel call (the scan body on non-TPU
backends, where the interpreted grid would unroll per batch), and
``packet_scatter_accum_scan`` drives a dense (n_batches, B) drain
schedule through either body as one ``lax.scan`` with the accumulator
carried in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# default packet-block size; callers that pre-pad ragged batches (e.g.
# StreamingAggregator.scatter_add) must pad to a multiple of this so the
# jitted wrapper sees few distinct shapes
BLOCK_PKTS = 128

# the one-hot routing products must not round payloads: on TPU a default
# f32 dot runs one bf16 pass (8 mantissa bits), so both the kernel and
# its jnp twin ask for full f32.  CPU backends compute f32 either way.
_F32_DOT = jax.lax.Precision.HIGHEST


def _packet_scatter_kernel(idx_ref, pkt_ref, init_ref, out_ref):
    del idx_ref, init_ref     # idx is consumed by the BlockSpec index maps
    out_ref[...] = pkt_ref[...]


def packet_scatter_pallas(packets: jnp.ndarray, idx: jnp.ndarray,
                          n_slots: int, *,
                          init: jnp.ndarray | None = None,
                          interpret: bool = False):
    """packets (N, W); idx (N,) int32 destination rows (< n_slots).

    Returns (n_slots, W) with row ``idx[n] = packets[n]``.  ``init`` is
    the destination buffer (zeros when omitted): it is aliased onto the
    output, so rows not covered by ``idx`` keep their ``init`` contents
    and no fresh zero-fill pass runs.  Duplicated indices are
    last-writer-wins in packet order (the later grid step's DMA lands
    last).
    """
    N, W = packets.shape
    if init is None:
        init = jnp.zeros((n_slots, W), packets.dtype)
    # one packet row per grid step, carried on a trailing unit axis: a
    # (1, 1, W) block spans the full extent of its last two dimensions,
    # which Mosaic requires of a row that is neither 8 sublanes nor the
    # whole array.  init rides along only to donate its buffer
    # (input_output_aliases); its block is never read, so a constant
    # index map lets Pallas fetch it once instead of one discarded row
    # DMA per packet
    row = (None, 1, W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[pl.BlockSpec(row, lambda i, idx_ref: (i, 0, 0)),
                  pl.BlockSpec(row, lambda i, idx_ref: (0, 0, 0))],
        out_specs=pl.BlockSpec(row, lambda i, idx_ref: (idx_ref[i], 0, 0)),
    )
    return pl.pallas_call(
        _packet_scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, W), packets.dtype),
        # operand indices count the scalar-prefetch arg: 0=idx, 1=packets
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), packets.reshape(N, 1, W),
      init.astype(packets.dtype).reshape(n_slots, 1, W)
      ).reshape(n_slots, W)


def _scatter_accum_body(idx_ref, w_ref, pkt, acc_in_ref, cnt_in_ref,
                        acc_ref, cnt_ref, *, exact: bool):
    """Shared grid-step body: route an f32 packet block into the live
    accumulator.  ``pkt`` (BN, W) f32 is already wire-decoded — the f32
    kernel passes the payload block through, the q8 kernel dequantizes
    rows first — so both wire formats share one accumulate dataflow.

    idx/w (1, BN); acc blocks (BS, W); cnt blocks (BS, 1).  The acc/cnt
    output blocks are revisited across the (innermost) packet-block
    dimension: copied from the live accumulator at the first packet
    block, then updated in VMEM for the rest of the sweep.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load_accumulator():
        acc_ref[...] = acc_in_ref[...]
        cnt_ref[...] = cnt_in_ref[...]

    BS = acc_ref.shape[0]
    base = pl.program_id(0) * BS
    rows = jax.lax.broadcasted_iota(jnp.int32, (BS, 1), 0) + base
    hits = idx_ref[...] == rows                       # (BS, BN) bool
    w = w_ref[...]                                    # (1, BN) f32
    whot = hits.astype(jnp.float32) * w               # weighted one-hot
    # the divisor sees every arrival, in both modes (§3.2.2 count rule)
    cnt_ref[...] += jnp.sum(whot, axis=1, keepdims=True)

    if exact:
        acc_ref[...] += jnp.dot(whot, pkt, precision=_F32_DOT,
                                preferred_element_type=jnp.float32)
    else:
        # Lock-free race, deterministic form: each writer reads the
        # call-entry snapshot (acc_in), so of all batch packets hitting a
        # slot only the last write survives — earlier adds are lost, the
        # paper's lost-update bias.
        valid = hits & (w > 0)
        colpos = jax.lax.broadcasted_iota(jnp.int32, valid.shape, 1) + 1
        lastcol = jnp.max(jnp.where(valid, colpos, 0), axis=1,
                          keepdims=True)              # (BS, 1); 0 = no hit
        lasthot = (colpos == lastcol) & valid
        contrib = jnp.dot(lasthot.astype(jnp.float32) * w, pkt,
                          precision=_F32_DOT,
                          preferred_element_type=jnp.float32)
        acc_ref[...] = jnp.where(lastcol > 0, acc_in_ref[...] + contrib,
                                 acc_ref[...])


def _scatter_accum_kernel(idx_ref, w_ref, pkt_ref, acc_in_ref, cnt_in_ref,
                          acc_ref, cnt_ref, *, exact: bool):
    """f32 wire format: the payload block is the packet block."""
    _scatter_accum_body(idx_ref, w_ref, pkt_ref[...].astype(jnp.float32),
                        acc_in_ref, cnt_in_ref, acc_ref, cnt_ref,
                        exact=exact)


def _scatter_accum_q8_kernel(idx_ref, w_ref, s_ref, pkt_ref, acc_in_ref,
                             cnt_in_ref, acc_ref, cnt_ref, *, exact: bool):
    """q8 wire format: fused dequantize-then-accumulate.

    ``s_ref`` (BN, 1) carries the per-packet symmetric scales; rows are
    dequantized (``q * scale``, the ``quantized_accum.py`` pattern) and
    THEN routed through the shared matmul body.  Dequantizing rows first
    — rather than folding the scale into the one-hot weights — keeps the
    result bitwise equal to dequantizing on the host and running the f32
    kernel, because the per-element IEEE ops are identical (f32 multiply
    is not associative across the dot contraction).
    """
    pkt = pkt_ref[...].astype(jnp.float32) * s_ref[...]
    _scatter_accum_body(idx_ref, w_ref, pkt, acc_in_ref, cnt_in_ref,
                        acc_ref, cnt_ref, exact=exact)


def packet_scatter_accum_pallas(packets: jnp.ndarray, idx: jnp.ndarray,
                                weights: jnp.ndarray, acc: jnp.ndarray,
                                counts: jnp.ndarray, *,
                                exact: bool = True,
                                block_slots: int = 8,
                                block_pkts: int = BLOCK_PKTS,
                                interpret: bool = False):
    """Scatter-accumulate one drained batch into a live accumulator.

    packets (N, W); idx (N,) int32 slot rows — entries with ``idx < 0``
    (ring padding) never match a slot; weights (N,) f32 per-arrival
    FedAvg weights (0 disables a packet entirely); acc (S, W) f32 and
    counts (S, 1) f32 are the live accumulator state.

    Returns (acc', counts').  N must be a multiple of ``block_pkts`` and
    S of ``block_slots`` (ops.py pads: packets with idx=-1, w=0; slots
    with zero rows).  Contract (DESIGN.md §3): slots no packet hits keep
    their accumulator value; duplicates add in ``exact`` mode and
    resolve last-writer-wins against the call-entry snapshot in
    ``approx`` mode, while counts always see every weighted arrival.
    """
    N, W = packets.shape
    S = acc.shape[0]
    assert N % block_pkts == 0, (N, block_pkts)
    assert S % block_slots == 0, (S, block_slots)
    n_pkt_blocks = N // block_pkts
    idx2d = idx.astype(jnp.int32).reshape(n_pkt_blocks, block_pkts)
    w2d = weights.astype(jnp.float32).reshape(n_pkt_blocks, block_pkts)
    grid = (S // block_slots, n_pkt_blocks)
    kernel = functools.partial(_scatter_accum_kernel, exact=exact)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_pkts), lambda s, j: (j, 0)),
            pl.BlockSpec((1, block_pkts), lambda s, j: (j, 0)),
            pl.BlockSpec((block_pkts, W), lambda s, j: (j, 0)),
            pl.BlockSpec((block_slots, W), lambda s, j: (s, 0)),
            pl.BlockSpec((block_slots, 1), lambda s, j: (s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_slots, W), lambda s, j: (s, 0)),
            pl.BlockSpec((block_slots, 1), lambda s, j: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, W), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(idx2d, w2d, packets, acc.astype(jnp.float32),
      counts.astype(jnp.float32))


def packet_scatter_accum_q8_pallas(packets: jnp.ndarray,
                                   scales: jnp.ndarray, idx: jnp.ndarray,
                                   weights: jnp.ndarray, acc: jnp.ndarray,
                                   counts: jnp.ndarray, *,
                                   exact: bool = True,
                                   block_slots: int = 8,
                                   block_pkts: int = BLOCK_PKTS,
                                   interpret: bool = False):
    """q8 twin of ``packet_scatter_accum_pallas`` (DESIGN.md §9).

    packets (N, W) **int8** wire payloads; scales (N,) f32 per-packet
    symmetric dequant scales (0 for ring padding).  Dequantization is
    fused into the accumulate grid step, so no f32 copy of the uplink
    ever materializes outside VMEM.  Same contract and same numerics as
    dequantizing host-side and calling the f32 kernel.
    """
    N, W = packets.shape
    S = acc.shape[0]
    assert N % block_pkts == 0, (N, block_pkts)
    assert S % block_slots == 0, (S, block_slots)
    n_pkt_blocks = N // block_pkts
    idx2d = idx.astype(jnp.int32).reshape(n_pkt_blocks, block_pkts)
    w2d = weights.astype(jnp.float32).reshape(n_pkt_blocks, block_pkts)
    # scales ride as an (N, 1) column so the block lands as (BN, 1) and
    # broadcasts against the (BN, W) payload with no in-kernel transpose
    s2d = scales.astype(jnp.float32).reshape(N, 1)
    grid = (S // block_slots, n_pkt_blocks)
    kernel = functools.partial(_scatter_accum_q8_kernel, exact=exact)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_pkts), lambda s, j: (j, 0)),
            pl.BlockSpec((1, block_pkts), lambda s, j: (j, 0)),
            pl.BlockSpec((block_pkts, 1), lambda s, j: (j, 0)),
            pl.BlockSpec((block_pkts, W), lambda s, j: (j, 0)),
            pl.BlockSpec((block_slots, W), lambda s, j: (s, 0)),
            pl.BlockSpec((block_slots, 1), lambda s, j: (s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_slots, W), lambda s, j: (s, 0)),
            pl.BlockSpec((block_slots, 1), lambda s, j: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, W), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(idx2d, w2d, s2d, packets.astype(jnp.int8), acc.astype(jnp.float32),
      counts.astype(jnp.float32))


def staleness_weights(weights: jnp.ndarray, staleness: jnp.ndarray,
                      rows: jnp.ndarray | None = None, *,
                      mode: str = "const", alpha: float = 0.5,
                      norm_clip: float = 1.0,
                      scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-packet effective FedAvg weight under async staleness
    (DESIGN.md §10).

    weights (...,) f32 base per-arrival weights; staleness (...,) f32
    the update's ``version-at-fold − version-at-send`` (>= 0).  Modes:

    - ``const``: ``w`` — FedBuff's unweighted buffer (staleness ignored).
    - ``poly``:  ``w · (1 + s)^(-alpha)`` — polynomial decay, the
      staleness correction of the FedBuff paper.
    - ``norm``:  poly × ``clip / max(clip, ‖row‖₂)`` — FedNS-style norm
      screening: a stale client whose update also grew large is damped
      harder (its drift dominates), while small stale updates pass.
      Needs ``rows`` (..., W); on the q8 wire pass ``scales`` (...,) so
      the norm is taken over the *dequantized* payload the accumulator
      actually sees.

    Shape-polymorphic and elementwise (the norm reduces axis -1 only),
    so the eager engine (per-window stacked arrays) and the compiled
    scan body ((R, B) schedule slices) compute identical f32 ops — the
    differential harness's bitwise claim covers the weighting too.
    Inert schedule padding (weight 0) stays inert in every mode.
    """
    w = jnp.asarray(weights, jnp.float32)
    if mode == "const":
        return w
    s = jnp.asarray(staleness, jnp.float32)
    fac = (1.0 + s) ** jnp.float32(-alpha)
    if mode == "poly":
        return w * fac
    if mode == "norm":
        assert rows is not None, "norm weighting needs payload rows"
        r = rows.astype(jnp.float32)
        if scales is not None:
            r = r * jnp.asarray(scales, jnp.float32)[..., None]
        nrm = jnp.sqrt(jnp.sum(r * r, axis=-1))
        clip = jnp.float32(norm_clip)
        return w * fac * (clip / jnp.maximum(clip, nrm))
    raise ValueError(f"unknown staleness mode {mode!r}")


def norm_clip_weights(weights: jnp.ndarray, rows: jnp.ndarray, *,
                      tau: float,
                      scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-packet norm-clipped FedAvg weight (DESIGN.md §11).

    weights (...,) f32 base per-arrival weights; rows (..., W) payload
    rows.  Each packet's contribution is scaled by
    ``tau / max(tau, ‖row‖₂)`` — the FedNS-style influence bound: a row
    inside the ball passes untouched (the factor is exactly 1.0), a
    boosted row is shrunk back to norm ``tau``, so one scaled-update
    attacker moves the per-slot aggregate by at most ``tau`` times its
    weight share.  On the q8 wire pass ``scales`` (...,) so the norm is
    taken over the *dequantized* payload the accumulator actually sees.

    Elementwise per packet (the norm reduces axis -1 only), so the
    eager engine (per-drain batches) and the compiled scan (whole
    schedule slices) compute identical f32 ops — the differential
    harness's bitwise claim covers the clipping.  Inert schedule
    padding (weight 0) stays inert.
    """
    w = jnp.asarray(weights, jnp.float32)
    r = rows.astype(jnp.float32)
    if scales is not None:
        r = r * jnp.asarray(scales, jnp.float32)[..., None]
    nrm = jnp.sqrt(jnp.sum(r * r, axis=-1))
    t = jnp.float32(tau)
    return w * (t / jnp.maximum(t, nrm))


def _robust_trim(m: jnp.ndarray, *, median: bool, beta: float
                 ) -> jnp.ndarray:
    """Per-slot trim depth t from the contributor count m (DESIGN.md
    §11): trimmed-mean drops ``floor(beta·m)`` ranks from each end;
    the coordinate-wise median is the degenerate trim that keeps only
    the middle rank (odd m) or middle pair (even m),
    ``t = floor((m-1)/2)``."""
    m = m.astype(jnp.float32)
    if median:
        t = jnp.floor((m - 1.0) * jnp.float32(0.5))
    else:
        t = jnp.floor(m * jnp.float32(beta))
    return jnp.maximum(t, 0.0)


def robust_finalize_jnp(table: jnp.ndarray, pres: jnp.ndarray, *,
                        median: bool = False, beta: float = 0.1
                        ) -> tuple:
    """Trimmed-mean / coordinate-wise-median finalize over the per-slot
    client table (DESIGN.md §11) — the jnp twin of
    ``robust_finalize_pallas``.

    table (S, K, W) f32: row (s, c) is client c's deduplicated payload
    for slot s (zeros where absent); pres (S, K) f32 > 0 marks present
    contributions.  Per slot and per coordinate the present values are
    rank-ordered (absent entries ride to the top past a +max sentinel),
    the lowest and highest ``t`` ranks are dropped
    (``t = floor(beta·m)``, or the median's middle-keep), and the
    survivors average.  Returns ``(agg (S, W), m (S,))`` with ``agg``
    zero where no contributor delivered (``m = 0``) — the caller's
    per-slot fallback mask, exactly like the mean path's counts.
    """
    K = table.shape[1]
    p = pres > 0
    m = jnp.sum(p.astype(jnp.float32), axis=1)            # (S,)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    vm = jnp.where(p[:, :, None], table, big)
    vs = jnp.sort(vm, axis=1)                             # absent last
    t = _robust_trim(m, median=median, beta=beta)
    ranks = jnp.arange(K, dtype=jnp.float32)[None, :, None]
    keep = ((ranks >= t[:, None, None])
            & (ranks < (m - t)[:, None, None]))           # (S, K, W)
    kept = jnp.sum(keep.astype(jnp.float32), axis=1)      # (S, W)
    ssum = jnp.sum(jnp.where(keep, vs, 0.0), axis=1)
    agg = ssum / jnp.maximum(kept, 1e-12)
    agg = jnp.where(kept > 0, agg, 0.0)
    return agg, m


def _robust_finalize_kernel(tab_ref, pres_ref, agg_ref, m_ref, vm_ref, *,
                            median: bool, beta: float):
    """Grid-step body of the fused robust finalize (one slot block by
    one lane tile).

    Rank selection without a sort: element (s, k, w)'s rank is the
    number of present values below it (ties broken by client order), a
    K-step ``fori_loop`` of (BS, K, WL) compares on the VPU — Mosaic has
    no in-kernel sort, and the rank pass selects the identical value
    multiset, so for exactly-representable sums the result is bitwise
    equal to the sorted jnp twin (the same caveat as the scatter
    kernels vs their twins).  The masked block is staged in the VMEM
    scratch ``vm_ref`` and each step loads client row j from there:
    Mosaic lowers a dynamic sublane slice of a ref, not of a value.
    """
    v = tab_ref[...]                                      # (BS, K, WL)
    pf = pres_ref[...]                                    # (BS, K)
    pres = pf > 0
    K = v.shape[1]
    # expand the f32 presence, not the mask: Mosaic has no shape cast
    # for a boolean (BS, K) -> (BS, K, 1)
    p3 = pf[:, :, None] > 0
    m = jnp.sum(pres.astype(jnp.float32), axis=1)         # (BS,)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    vm = jnp.where(p3, v, big)
    vm_ref[...] = vm
    kiota = jax.lax.broadcasted_iota(jnp.int32, vm.shape, 1)

    def rank_step(j, rank):
        vj = vm_ref[:, pl.ds(j, 1), :]                    # (BS, 1, WL)
        below = (vj < vm) | ((vj == vm) & (j < kiota))
        return rank + below.astype(jnp.float32)

    rank = jax.lax.fori_loop(0, K, rank_step,
                             jnp.zeros(vm.shape, jnp.float32))
    t = _robust_trim(m, median=median, beta=beta)[:, None, None]
    keep = (rank >= t) & (rank < m[:, None, None] - t) & p3
    kept = jnp.sum(keep.astype(jnp.float32), axis=1)      # (BS, W)
    ssum = jnp.sum(jnp.where(keep, v, 0.0), axis=1)
    agg = ssum / jnp.maximum(kept, 1e-12)
    agg_ref[...] = jnp.where(kept > 0, agg, 0.0)
    m_ref[...] = m[:, None]


def robust_finalize_pallas(table: jnp.ndarray, pres: jnp.ndarray, *,
                           median: bool = False, beta: float = 0.1,
                           block_slots: int = 8,
                           interpret: bool = False) -> tuple:
    """Fused trimmed-mean / median finalize kernel (DESIGN.md §11).

    table (S, K, W) f32 per-slot client table; pres (S, K) f32
    presence.  S must be a multiple of ``block_slots`` (callers pad
    with inert zero slots).  Grid over (slot block, 128-lane tile);
    each step holds its (BS, K, 128) table block in VMEM, rank-selects
    the trimmed band per coordinate and averages it — no (S, K, W)
    intermediate ever leaves VMEM, and the block's VMEM footprint
    stays within budget up to K = 256.  Coordinates are independent,
    so the last, partial lane tile (W = 367 is not a multiple of 128)
    needs no padded copy of the table: its out-of-range lanes are never
    written back.  Returns ``(agg (S, W), m (S,))`` like the jnp twin.
    """
    S, K, W = table.shape
    assert S % block_slots == 0, (S, block_slots)
    lanes = min(W, 128)
    kernel = functools.partial(_robust_finalize_kernel, median=median,
                               beta=beta)
    agg, m = pl.pallas_call(
        kernel,
        grid=(S // block_slots, pl.cdiv(W, lanes)),
        in_specs=[
            pl.BlockSpec((block_slots, K, lanes), lambda s, l: (s, 0, l)),
            pl.BlockSpec((block_slots, K), lambda s, l: (s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_slots, lanes), lambda s, l: (s, l)),
            pl.BlockSpec((block_slots, 1), lambda s, l: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, W), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_slots, K, lanes), jnp.float32)],
        interpret=interpret,
    )(table.astype(jnp.float32), pres.astype(jnp.float32))
    return agg, m[:, 0]


def packet_scatter_accum_batch_jnp(packets: jnp.ndarray, idx: jnp.ndarray,
                                   weights: jnp.ndarray, acc: jnp.ndarray,
                                   counts: jnp.ndarray, *,
                                   exact: bool = True):
    """jnp twin of one ``packet_scatter_accum_pallas`` call.

    Same dataflow as ``_scatter_accum_kernel`` — one-hot (slot × packet)
    routing matrix, unconditional counts, exact add or last-writer-wins
    against the call-entry snapshot — expressed as plain jnp over the
    whole (S, N) hit matrix instead of the blocked grid.  This is the
    scan body used on backends where the Pallas kernel would run in
    interpret mode (the grid unrolls into hundreds of HLO ops per
    batch); the contract is identical, and for payloads whose sums are
    exactly representable in f32 (integer-valued tests) the result is
    bitwise equal to the kernel for any block tiling
    (tests/test_engine_compiled.py).

    packets (N, W); idx (N,) int32 (< 0 = inert padding); weights (N,)
    f32; acc (S, W) f32; counts (S, 1) f32.  Returns (acc', counts').
    """
    S = acc.shape[0]
    N = idx.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, N), 0)
    hits = idx[None, :].astype(jnp.int32) == rows         # (S, N) bool
    w = weights[None, :].astype(jnp.float32)              # (1, N)
    whot = hits.astype(jnp.float32) * w
    counts = counts + jnp.sum(whot, axis=1, keepdims=True)
    pkt = packets.astype(jnp.float32)
    if exact:
        acc = acc + jnp.dot(whot, pkt, precision=_F32_DOT,
                            preferred_element_type=jnp.float32)
    else:
        valid = hits & (w > 0)
        colpos = jax.lax.broadcasted_iota(jnp.int32, (S, N), 1) + 1
        lastcol = jnp.max(jnp.where(valid, colpos, 0), axis=1,
                          keepdims=True)                  # (S, 1); 0 = no hit
        lasthot = (colpos == lastcol) & valid
        contrib = jnp.dot(lasthot.astype(jnp.float32) * w, pkt,
                          precision=_F32_DOT,
                          preferred_element_type=jnp.float32)
        # ``acc`` here is the call-entry snapshot, so this reproduces
        # the kernel's deterministic lock-free race exactly
        acc = jnp.where(lastcol > 0, acc + contrib, acc)
    return acc, counts


def packet_scatter_accum_batch_q8_jnp(packets: jnp.ndarray,
                                      scales: jnp.ndarray,
                                      idx: jnp.ndarray,
                                      weights: jnp.ndarray,
                                      acc: jnp.ndarray,
                                      counts: jnp.ndarray, *,
                                      exact: bool = True):
    """jnp twin of one ``packet_scatter_accum_q8_pallas`` call:
    elementwise dequantize (``q * scale``), then the shared f32 batch
    dataflow — the same op order as the fused kernel, so the two are
    bitwise equal for any block tiling."""
    pkt = packets.astype(jnp.float32) * scales.astype(jnp.float32)[:, None]
    return packet_scatter_accum_batch_jnp(pkt, idx, weights, acc, counts,
                                          exact=exact)


def packet_table_scatter(sched_idx: jnp.ndarray, sched_w: jnp.ndarray,
                         sched_pk: jnp.ndarray, acc: jnp.ndarray,
                         cnt: jnp.ndarray, *,
                         sched_scales: jnp.ndarray | None = None):
    """One-shot fold of a *unique-index* drain schedule (the robust
    table fold, DESIGN.md §11).

    The combined ``slot·K + client`` indices hit each accumulator row
    at most once (dedup upstream), so the whole schedule folds as ONE
    flat scatter-add — no batch scan, no (S, N) one-hot routing matrix.
    That matters here: the table accumulator has ``S·K`` rows, and the
    per-batch one-hot twin the mean path uses would pay an
    ``O(S·K · B)`` routing product per drained batch.  Bitwise equal to
    running the batches through ``packet_scatter_accum_scan``: every
    real row lands as ``0 + 1.0·payload`` either way.

    Padding entries carry ``idx < 0``; ``.at[]`` would WRAP a negative
    index to the end of the buffer, so they are routed to the buffer's
    last row — the caller passes one extra dustbin row and slices it
    off (their weight is 0.0, so the dustbin only ever accumulates
    zeros anyway).
    """
    W = acc.shape[1]
    idx = sched_idx.reshape(-1).astype(jnp.int32)
    w = sched_w.reshape(-1).astype(jnp.float32)
    pk = sched_pk.reshape(-1, W).astype(jnp.float32)
    if sched_scales is not None:
        pk = pk * sched_scales.reshape(-1).astype(jnp.float32)[:, None]
    dust = jnp.where(idx >= 0, idx, jnp.int32(acc.shape[0] - 1))
    acc = acc.at[dust].add(w[:, None] * pk)
    cnt = cnt.at[dust, 0].add(w)
    return acc, cnt


def packet_scatter_accum_scan(sched_idx: jnp.ndarray, sched_w: jnp.ndarray,
                              sched_pk: jnp.ndarray, acc: jnp.ndarray,
                              counts: jnp.ndarray, *,
                              sched_scales: jnp.ndarray | None = None,
                              exact: bool = True,
                              use_pallas: bool = False,
                              block_slots: int = 8,
                              block_pkts: int = BLOCK_PKTS,
                              interpret: bool = False):
    """Run a whole round's drain schedule as one ``lax.scan``.

    sched_idx/sched_w (n_batches, B) and sched_pk (n_batches, B, W) are
    the dense drain schedule (core/engine_compiled.py): each row is one
    drained ring batch, padded with inert ``idx = -1`` / ``weight = 0``
    entries.  acc (S, W) and counts (S, 1) are the live accumulator
    carried through the scan — XLA keeps the carry buffers in place, so
    no per-drain (S, W) reallocation happens.  ``use_pallas`` selects
    the Pallas grid kernel (the production TPU body; S must then be a
    multiple of ``block_slots`` and B of ``block_pkts``) vs the jnp
    twin; both implement the same DESIGN.md §3 contract per batch.

    When ``sched_scales`` (n_batches, B) is given, sched_pk carries the
    int8 wire payloads and each batch dequantizes inside the scan body
    (the q8 kernel / its jnp twin) — the f32 uplink never materializes
    as a whole-round tensor (DESIGN.md §9).
    """
    q8 = sched_scales is not None
    if use_pallas:
        def step(carry, batch):
            a, c = carry
            if q8:
                bidx, bw, bsc, bpk = batch
                a, c = packet_scatter_accum_q8_pallas(
                    bpk, bsc, bidx, bw, a, c, exact=exact,
                    block_slots=block_slots, block_pkts=block_pkts,
                    interpret=interpret)
            else:
                bidx, bw, bpk = batch
                a, c = packet_scatter_accum_pallas(
                    bpk, bidx, bw, a, c, exact=exact,
                    block_slots=block_slots,
                    block_pkts=block_pkts, interpret=interpret)
            return (a, c), None
    else:
        def step(carry, batch):
            a, c = carry
            if q8:
                bidx, bw, bsc, bpk = batch
                a, c = packet_scatter_accum_batch_q8_jnp(
                    bpk, bsc, bidx, bw, a, c, exact=exact)
            else:
                bidx, bw, bpk = batch
                a, c = packet_scatter_accum_batch_jnp(bpk, bidx, bw, a, c,
                                                      exact=exact)
            return (a, c), None
    xs = ((sched_idx, sched_w, sched_scales, sched_pk) if q8
          else (sched_idx, sched_w, sched_pk))
    (acc, counts), _ = jax.lax.scan(step, (acc, counts), xs)
    return acc, counts


def combine_partials(acc_parts: jnp.ndarray, cnt_parts: jnp.ndarray,
                     axis_name=None, axis=0):
    """Merge per-shard partial sums (the paper's per-core combine, §3.2).

    Inside ``shard_map`` the partials live one-per-device and the merge
    is a single ``psum`` per mesh level over ``axis_name`` — a string
    for the 1-D worker mesh, or a sequence (innermost level first, e.g.
    ``('worker', 'host')``, DESIGN.md §12) for the hierarchical mesh.
    In the single-device emulation the partials carry leading shard
    axes and the merge is a plain sum over ``axis`` (an int or a
    sequence of ints, summed innermost/highest axis first to mirror the
    psum order).  Every ordering adds exactly one partial per leaf, so
    for payloads whose sums are exactly representable in f32
    (integer-valued test streams) all paths are bitwise identical.
    """
    if axis_name is not None:
        names = ((axis_name,) if isinstance(axis_name, str)
                 else tuple(axis_name))
        for name in names:
            acc_parts = jax.lax.psum(acc_parts, name)
            cnt_parts = jax.lax.psum(cnt_parts, name)
        return acc_parts, cnt_parts
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    for ax in sorted(axes, reverse=True):
        acc_parts = jnp.sum(acc_parts, axis=ax)
        cnt_parts = jnp.sum(cnt_parts, axis=ax)
    return acc_parts, cnt_parts


def packet_scatter_accum_sharded(sched_idx: jnp.ndarray,
                                 sched_w: jnp.ndarray,
                                 sched_pk: jnp.ndarray, acc: jnp.ndarray,
                                 counts: jnp.ndarray, *,
                                 sched_scales: jnp.ndarray | None = None,
                                 mesh=None, axis_name: str = "worker",
                                 exact: bool = True,
                                 use_pallas: bool = False,
                                 block_slots: int = 8,
                                 block_pkts: int = BLOCK_PKTS,
                                 interpret: bool = False):
    """Sharded round scan: per-shard partial sums + one combine at END.

    sched_idx/sched_w (n_shards, R, B) and sched_pk (n_shards, R, B, W)
    carry the drain schedule demuxed per shard
    (``engine_compiled.shard_schedule``): shard s owns the drain batches
    of the worker rings mapped to it, padded to a common row count R
    with inert rows.  Each shard folds its slice through the unsharded
    scan body (``packet_scatter_accum_scan``) into **zero-initialized
    shard-local (total, counts) partials** — the DPU's per-core
    accumulators — and the partials are merged by ``combine_partials``:
    a ``psum`` over the ``'worker'`` mesh axis when ``mesh`` is given
    (real devices, via ``shard_map``), else a sum over the leading shard
    axis (vmap emulation, any device count).  The incoming ``acc`` /
    ``counts`` are added after the combine.

    Exactness: both modes' per-batch contributions are additive — exact
    adds every weighted arrival, approx adds exactly one last-writer
    contribution per (slot, drained batch) — so regrouping batches by
    shard changes only f32 summation order.  On integer-valued payloads
    the result is bitwise identical to the unsharded scan over the same
    schedule, in both modes (tests/test_engine_sharded.py).
    """
    body = functools.partial(
        packet_scatter_accum_scan, exact=exact, use_pallas=use_pallas,
        block_slots=block_slots, block_pkts=block_pkts, interpret=interpret)
    zero_acc = jnp.zeros_like(acc)
    zero_cnt = jnp.zeros_like(counts)
    q8 = sched_scales is not None
    # check_vma=False: the scan carries start from the replicated zeros
    # and leave varying per shard, which shard_map's varying-axis typing
    # rejects as a carry type change; the psum makes the outputs
    # replicated again, as out_specs=P() says
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(axis_name)
        if q8:
            def shard_fn(bidx, bw, bsc, bpk):
                # leading shard axis is size 1 on each device
                a, c = body(bidx[0], bw[0], bpk[0], zero_acc, zero_cnt,
                            sched_scales=bsc[0])
                return combine_partials(a, c, axis_name=axis_name)

            a, c = jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec, spec, spec, spec),
                out_specs=(P(), P()), check_vma=False,
            )(sched_idx, sched_w, sched_scales, sched_pk)
        else:
            def shard_fn(bidx, bw, bpk):
                # leading shard axis is size 1 on each device
                a, c = body(bidx[0], bw[0], bpk[0], zero_acc, zero_cnt)
                return combine_partials(a, c, axis_name=axis_name)

            a, c = jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=(P(), P()), check_vma=False,
            )(sched_idx, sched_w, sched_pk)
    elif q8:
        a_parts, c_parts = jax.vmap(
            lambda bidx, bw, bsc, bpk: body(bidx, bw, bpk, zero_acc,
                                            zero_cnt, sched_scales=bsc)
        )(sched_idx, sched_w, sched_scales, sched_pk)
        a, c = combine_partials(a_parts, c_parts)
    else:
        a_parts, c_parts = jax.vmap(
            lambda bidx, bw, bpk: body(bidx, bw, bpk, zero_acc, zero_cnt)
        )(sched_idx, sched_w, sched_pk)
        a, c = combine_partials(a_parts, c_parts)
    return acc + a, counts + c


def packet_scatter_accum_hier(sched_idx: jnp.ndarray,
                              sched_w: jnp.ndarray,
                              sched_pk: jnp.ndarray, acc: jnp.ndarray,
                              counts: jnp.ndarray, *,
                              sched_scales: jnp.ndarray | None = None,
                              mesh=None, host_axis: str = "host",
                              worker_axis: str = "worker",
                              exact: bool = True,
                              use_pallas: bool = False,
                              block_slots: int = 8,
                              block_pkts: int = BLOCK_PKTS,
                              interpret: bool = False):
    """Hierarchical round scan over a 2-D (host, worker) mesh
    (DESIGN.md §12).

    sched_idx/sched_w (H, S, R, B) and sched_pk (H, S, R, B, W) carry
    the drain schedule partitioned twice: by client-range ownership
    across the H hosts (``engine_compiled.partition_schedule_by_host``)
    and then by ring ownership across each host's S worker shards
    (``engine_compiled.shard_schedule``), each (h, s) slice padded to a
    common row count R with inert rows.  Each leaf folds its slice
    through the unsharded scan body into zero-initialized leaf-local
    ``(total, counts)`` partials, then ``combine_partials`` merges with
    **one psum per mesh level** — worker-level within a host row first,
    host-level across rows second — mirroring the paper's per-core
    combine followed by the cross-machine combine.  Without a mesh the
    emulation nests two vmaps and sums the two leading axes in the same
    innermost-first order.

    Exactness: both partitions only regroup the same additive per-batch
    contributions, so on payloads whose sums are exactly representable
    in f32 any (hosts, shards) factorization is bitwise identical to
    the unsharded scan over the same arrivals
    (tests/test_engine_hier.py).
    """
    body = functools.partial(
        packet_scatter_accum_scan, exact=exact, use_pallas=use_pallas,
        block_slots=block_slots, block_pkts=block_pkts, interpret=interpret)
    zero_acc = jnp.zeros_like(acc)
    zero_cnt = jnp.zeros_like(counts)
    q8 = sched_scales is not None
    # check_vma=False: the scan carries start from the replicated zeros
    # and leave varying per shard, which shard_map's varying-axis typing
    # rejects as a carry type change; the psum makes the outputs
    # replicated again, as out_specs=P() says
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(host_axis, worker_axis)
        levels = (worker_axis, host_axis)     # innermost level first
        if q8:
            def shard_fn(bidx, bw, bsc, bpk):
                # both leading mesh axes are size 1 on each device
                a, c = body(bidx[0, 0], bw[0, 0], bpk[0, 0], zero_acc,
                            zero_cnt, sched_scales=bsc[0, 0])
                return combine_partials(a, c, axis_name=levels)

            a, c = jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec, spec, spec, spec),
                out_specs=(P(), P()), check_vma=False,
            )(sched_idx, sched_w, sched_scales, sched_pk)
        else:
            def shard_fn(bidx, bw, bpk):
                a, c = body(bidx[0, 0], bw[0, 0], bpk[0, 0], zero_acc,
                            zero_cnt)
                return combine_partials(a, c, axis_name=levels)

            a, c = jax.shard_map(
                shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=(P(), P()), check_vma=False,
            )(sched_idx, sched_w, sched_pk)
    elif q8:
        fold = jax.vmap(jax.vmap(
            lambda bidx, bw, bsc, bpk: body(bidx, bw, bpk, zero_acc,
                                            zero_cnt, sched_scales=bsc)))
        a_parts, c_parts = fold(sched_idx, sched_w, sched_scales, sched_pk)
        a, c = combine_partials(a_parts, c_parts, axis=(0, 1))
    else:
        fold = jax.vmap(jax.vmap(
            lambda bidx, bw, bpk: body(bidx, bw, bpk, zero_acc, zero_cnt)))
        a_parts, c_parts = fold(sched_idx, sched_w, sched_pk)
        a, c = combine_partials(a_parts, c_parts, axis=(0, 1))
    return acc + a, counts + c
