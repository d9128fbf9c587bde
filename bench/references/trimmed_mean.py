"""Plain numpy reference of a synchronous FedAvg round under Yin et
al.'s coordinate-wise trimmed mean (arXiv:1803.01498), independent of
the server's code.

Per slot, ``m`` clients delivered it (each (client, slot) once, however
often it arrived).  Per coordinate their values are sorted, the lowest
and the highest ``t = floor(BETA * m)`` are dropped, and the rest are
averaged.  A slot that no client delivered (``m = 0``) keeps the
previous global.  A q8 packet is worth ``int8 value * its scale``.
Values are sorted and summed in float64, so the reference's own
rounding is far below the float32 server's.

``control`` is the same round computed one precision step below the
configuration's float32, as in ``fedavg_mean``: each value as a
three-pass bfloat16 product keeps it, ``bf16(x) + bf16(x - bf16(x))``,
ranked and summed in float32.  ``chain`` and ``gap`` are
``fedavg_mean``'s: a trimmed round, like a mean round, replaces a slot
that someone delivered and keeps the rest.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_fedavg_mean", Path(__file__).with_name("fedavg_mean.py"))
_mean = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mean)

BETA = 0.1               # the fraction trimmed from each side
RoundRef = _mean.RoundRef
chain = _mean.chain
gap = _mean.gap


def trim_depth(m: np.ndarray) -> np.ndarray:
    """Values dropped from each side of a slot that ``m`` clients
    delivered: ``floor(BETA * m)``."""
    return np.floor(BETA * m).astype(np.int64)


def round_reference(payloads: np.ndarray, scales, accepted: np.ndarray,
                    *, high: bool = False) -> RoundRef:
    """payloads (K, N, W) f32 or int8; scales (K, N) f32 or None;
    accepted (K, N) bool.  ``high`` computes the control instead.
    ``.mean`` holds the trimmed mean, ``.scale`` the mean |kept value|
    (0 where m = 0), ``.counts`` m per slot."""
    k, n, w = payloads.shape
    mean = np.zeros((n, w), np.float64)
    scale = np.zeros((n, w), np.float64)
    counts = accepted.sum(axis=0)
    step = max(1, _mean.BLOCK_VALUES // (k * w))
    ranks = np.arange(k)[None, :, None]
    for a in range(0, n, step):
        b = min(n, a + step)
        x = _values(payloads, scales, a, b, high)         # (b-a, W, K)
        # absent clients sort last, past every delivered value
        x = np.where(accepted[:, a:b].T[:, None, :], x, np.inf)
        x.sort(axis=-1)
        x = x.transpose(0, 2, 1)                           # (b-a, K, W)
        m = counts[a:b][:, None, None]
        t = trim_depth(m)
        keep = (ranks >= t) & (ranks < m - t)              # (b-a, K, 1)
        kept = np.maximum(m - 2 * t, 1)[:, :, 0]
        x = np.where(keep, x, 0)
        mean[a:b] = x.sum(axis=1, dtype=x.dtype) / kept.astype(x.dtype)
        if not high:
            scale[a:b] = np.abs(x).sum(axis=1) / kept
    return RoundRef(mean.reshape(-1), scale.reshape(-1), counts)


def _values(payloads, scales, a, b, high) -> np.ndarray:
    """Slots [a, b) as (slot, coordinate, client) values: float64, or
    for the control float32 as a three-pass bfloat16 product keeps
    them."""
    dtype = np.float32 if high else np.float64
    x = payloads[:, a:b].astype(dtype)
    if scales is not None:
        x *= scales[:, a:b, None].astype(dtype)
    if high:
        hi = _mean.to_bf16(x)
        x = hi + _mean.to_bf16(x - hi)
    return np.ascontiguousarray(x.transpose(1, 2, 0))
