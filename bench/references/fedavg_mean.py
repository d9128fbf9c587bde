"""Plain numpy reference of a synchronous FedAvg round with unit
weights, independent of the server's code.

Per slot, the new global is the mean of the packets that reached the
server (each (client, slot) once, however often it was delivered);
slots that no client delivered keep the previous global.  A q8 packet
is worth ``int8 value * its scale``.  Sums run in float64, so the
reference's own rounding is far below the float32 server's.

``control`` is the same round computed one precision step lower, as a
later change might be tempted to: the configuration states float32
accumulation at ``Precision.HIGHEST``; the control keeps only what a
three-pass bfloat16 product (``Precision.HIGH``) keeps of each value,
``bf16(x) + bf16(x - bf16(x))``, and sums in float32.  A comparison
that cannot tell the control from the server is too loose.
"""
from __future__ import annotations

import dataclasses

import numpy as np

BLOCK_VALUES = 1 << 22   # values per block: bounds the float64 temporaries


@dataclasses.dataclass
class RoundRef:
    mean: np.ndarray      # (N*W,) float64 per-element mean of accepted
    scale: np.ndarray     # (N*W,) float64 mean |value| of accepted
    counts: np.ndarray    # (N,) accepted packets per slot


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    bits = x.view(np.uint32)
    bits = bits + (((bits >> 16) & 1) + np.uint32(0x7FFF))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def round_reference(payloads: np.ndarray, scales, accepted: np.ndarray,
                    *, high: bool = False) -> RoundRef:
    """payloads (K, N, W) f32 or int8; scales (K, N) f32 or None;
    accepted (K, N) bool.  ``high`` computes the control instead."""
    k, n, w = payloads.shape
    mean = np.zeros((n, w), np.float64)
    scale = np.zeros((n, w), np.float64)
    counts = accepted.sum(axis=0)
    step = max(1, BLOCK_VALUES // (k * w))
    for a in range(0, n, step):
        b = min(n, a + step)
        cnt = np.maximum(counts[a:b], 1)[:, None]
        if high:
            mean[a:b] = _control_sums(payloads, scales, accepted, a, b) / \
                cnt.astype(np.float32)
            continue
        # per slot, the accepted clients' weights (q8: their scales)
        # times their packets: one (1, K) @ (K, W) product a slot
        wt = accepted[:, a:b].astype(np.float64)
        if scales is not None:
            wt *= scales[:, a:b]
        wt = wt.T[:, None, :]                                  # (b, 1, K)
        x = payloads[:, a:b].transpose(1, 0, 2).astype(np.float64)
        mean[a:b] = np.matmul(wt, x)[:, 0] / cnt
        np.abs(x, out=x)
        scale[a:b] = np.matmul(np.abs(wt), x)[:, 0] / cnt
    return RoundRef(mean.reshape(-1), scale.reshape(-1), counts)


def _control_sums(payloads, scales, accepted, a, b) -> np.ndarray:
    """Per-slot sums of slots [a, b) as a three-pass bfloat16 product
    keeps the values, summed in float32 in client order."""
    x = payloads[:, a:b].astype(np.float32)
    if scales is not None:
        x = x * scales[:, a:b, None]
    hi = to_bf16(x)
    x = (hi + to_bf16(x - hi)) * accepted[:, a:b, None]
    return x.sum(axis=0, dtype=np.float32)


def chain(refs: list, g0: np.ndarray, sequence, n_params: int):
    """Expected global after each round of ``sequence`` (indices into
    ``refs``), starting from ``g0``.  Yields (value, scale) per round;
    scale 0 marks elements that must equal ``g0`` or a zero mean
    exactly."""
    value = g0.astype(np.float64)
    scale = np.zeros(n_params, np.float64)
    for p in sequence:
        r = refs[p]
        w = r.mean.size // r.counts.size
        have = np.repeat(r.counts > 0, w)[:n_params]
        value = np.where(have, r.mean[:n_params], value)
        scale = np.where(have, r.scale[:n_params], scale)
        yield value, scale


def gap(got: np.ndarray, value: np.ndarray, scale: np.ndarray) -> float:
    """Largest error of ``got`` in units of the mean |value| summed into
    each element; inf where an element that must be exact is not."""
    d = np.abs(got.astype(np.float64) - value)
    exact = scale == 0
    if np.any(d[exact] != 0) or not np.all(np.isfinite(got)):
        return float("inf")
    live = ~exact
    return float((d[live] / scale[live]).max()) if live.any() else 0.0
