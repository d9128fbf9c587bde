"""Smoke check of the served FedAvg round on a TPU chip.

Drives the compiled round through its user entry point
(``EngineConfig(compile=True)`` -> ``run_engine_round`` ->
``demux_events`` -> the jitted fold) at the paper CNN's full update:
``flatten_pytree(init_cnn(key, CONFIG))``, 4,585,546 f32 values per
client, K = 10 clients, an uplink with 4.68% loss and 5% duplication
built from ``--seed``.  Phases, each checked against a reference:

  a. exact mode, f32 wire (W = 367) vs a plain numpy weighted mean:
     on integer-valued payloads bitwise when the reference's exact sums
     are divided by the device's own f32 divide, and within one ulp of
     numpy's quotient; within ``K * eps32 * max|x|`` on the CNN's
     normal-distributed weights;
  b. approx mode (the lock-free race), bitwise vs the same round with
     the jnp scan body, and its error against phase a;
  c. exact mode, q8 wire (W = 1464), bitwise vs decoding the wire on
     the host and running the f32 round (DESIGN.md §9);
  d. trimmed mean (DESIGN.md §11) vs a numpy sort-based trimmed mean,
     within ``K * eps32 * max|x|``.

``--chips 4`` runs only the sharded rounds instead: ``shards=4`` on the
``('worker',)`` mesh and ``hosts=2, shards=2`` on the 2-D mesh, each
bitwise equal to ``shards=1`` on integer payloads, each holding an
all-reduce.

Each phase prints one JSON line: its first call's seconds, the backend
compile seconds and persistent-cache hits within that call, the warm
round's seconds, the error against its reference, and the share of the
dense sweep's slot-block visits the fold grid makes on the round's
drain schedule (before any shard split), and the demux's host buffer
pool so far: its leases and how many reused a pooled buffer
(``engine_compiled.host_pool_stats``).  The last line is
``{"ok": true, "device": {...}}``.  Any mismatch or exception exits
nonzero, and so does a run that finds no TPU or whose round does not
hold the Pallas kernel (``tpu_custom_call``).

Run:  python chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LOSS_RATE = 0.0468          # examples/packet_server.py's uplink
DUP_RATE = 0.05
INT_SCALE = 2520            # lcm(1..10): every per-slot mean of K <= 10
                            # integer payloads is exact in f32


class SmokeFailure(AssertionError):
    """A phase's result disagrees with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass
class Smoke:
    """What every phase shares: the clients' updates and the checks.

    ``on_chip`` is False only for rehearsals on the CPU at a small
    ``cnn``; the checks that the round runs the Pallas kernel then
    have nothing to find and are skipped.
    """
    seed: int
    cnn: object
    k: int = 10
    on_chip: bool = True
    scan_body: str = "auto"

    def __post_init__(self):
        import jax
        from repro.core.packets import flatten_pytree
        from repro.models.cnn import init_cnn
        key = jax.random.PRNGKey(self.seed)
        flats = [np.asarray(flatten_pytree(
            init_cnn(jax.random.fold_in(key, c), self.cnn))[0])
            for c in range(self.k + 1)]
        self.normal = np.stack(flats[:self.k])          # (K, P) f32
        self.prev = flats[self.k]
        self.n_params = self.normal.shape[1]
        rng = np.random.default_rng(self.seed)
        self.ints = (INT_SCALE * rng.integers(-8, 9, self.normal.shape)
                     ).astype(np.float32)
        self.prev_int = (INT_SCALE * rng.integers(-8, 9, self.n_params)
                         ).astype(np.float32)

    def cfg(self, payload: int, **kw):
        from repro.core.server import EngineConfig
        kw.setdefault("scan_body", self.scan_body)
        return EngineConfig(n_clients=self.k, n_params=self.n_params,
                            payload=payload, compile=True, **kw)

    def stream(self, pk, scales=None):
        """One round's lossy uplink; the same seed gives every stream
        the same loss, duplication and order."""
        from repro.core.server import make_uplink_stream
        events, up = make_uplink_stream(
            np.random.default_rng(self.seed + 1), pk, loss_rate=LOSS_RATE,
            dup_rate=DUP_RATE, scales=scales)
        return events, np.asarray(up) > 0

    def check_round(self, cfg, events, prev, collective: bool = False):
        """The round this config serves runs the Pallas fold (and, on a
        mesh, combines with an all-reduce), not a stand-in."""
        if not self.on_chip:
            return
        import jax.numpy as jnp
        from repro.core.engine_compiled import (_use_pallas, demux_events,
                                                lower_round)
        check(_use_pallas(cfg), "the round would not use the Pallas fold")
        sched, _, _ = demux_events(cfg, events)
        text = lower_round(cfg, sched,
                           jnp.zeros((cfg.n_slots, cfg.payload)),
                           jnp.zeros((cfg.n_slots,)), prev).as_text()
        check("tpu_custom_call" in text,
              "the lowered round holds no tpu_custom_call")
        if collective:
            check("all_reduce" in text or "all-reduce" in text,
                  "the lowered sharded round holds no all-reduce")


def packetize_np(flats: np.ndarray, payload: int) -> np.ndarray:
    """(K, P) -> (K, N, W), zero-padded tail (packets.packetize)."""
    k, p = flats.shape
    n = -(-p // payload)
    out = np.zeros((k, n * payload), np.float32)
    out[:, :p] = flats
    return out.reshape(k, n, payload)


def timed(cfg, flats, prev, events):
    """One served round, timed to ``block_until_ready``."""
    from repro.core.server import run_engine_round
    t0 = time.perf_counter()
    res = run_engine_round(cfg, flats, prev, events)
    res.new_global.block_until_ready()
    return res, time.perf_counter() - t0


@contextlib.contextmanager
def compile_log():
    """Seconds JAX spent in backend compiles, and the executables it
    loaded from the persistent cache instead (a hit's seconds are its
    load time), read from JAX's monitoring events."""
    from jax import monitoring
    log = dict(compile_s=0.0, cache_hits=0)

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            log["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            log["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def serve(cfg, flats, prev, events):
    """The same round cold (compiling), then warm."""
    with compile_log() as log:
        _, cold = timed(cfg, flats, prev, events)
    res, warm = timed(cfg, flats, prev, events)
    return res, dict(first_call_s=cold, round_s=warm, **log)


def mean_reference(pk: np.ndarray, up: np.ndarray, prev: np.ndarray,
                   n_params: int, divide=np.divide):
    """Per slot, the mean of the delivered clients' packets; slots no
    client delivered keep ``prev`` (unit FedAvg weights).  ``divide``
    takes the per-slot sums and counts to the mean."""
    total = np.sum(pk * up[:, :, None].astype(pk.dtype), axis=0)
    counts = up.sum(axis=0).astype(pk.dtype)
    avg = np.asarray(divide(total, np.maximum(counts, 1)[:, None]))
    avg = avg.reshape(-1)[:n_params]
    have = np.repeat(counts > 0, pk.shape[2])[:n_params]
    return np.where(have, avg, prev), counts


def trimmed_reference(pk: np.ndarray, up: np.ndarray, prev: np.ndarray,
                      n_params: int, beta: float):
    """Sort-based trimmed mean in float64: per slot and coordinate, drop
    ``floor(beta * m)`` of the m delivered values from each end."""
    vals = np.where(up[:, :, None], pk.astype(np.float64), np.inf)
    vals = np.sort(vals.transpose(1, 0, 2), axis=1)        # (N, K, W)
    m = up.sum(axis=0)
    t = np.floor(np.float32(m) * np.float32(beta)).astype(np.int64)
    ranks = np.arange(pk.shape[0])[None, :]
    keep = (ranks >= t[:, None]) & (ranks < (m - t)[:, None])
    kept = np.where(keep[:, :, None], vals, 0.0).sum(axis=1)
    avg = kept / np.maximum(keep.sum(axis=1), 1)[:, None]
    have = np.repeat(m > 0, pk.shape[2])[:n_params]
    return np.where(have, avg.reshape(-1)[:n_params], prev), m


def device_divide(total: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """The round's END divide as the device computes it.  A TPU's f32
    divide is not correctly rounded (it can land one ulp from numpy's
    quotient), so the bitwise check of the fold divides the reference's
    exact integer sums the same way."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(jnp.divide)(total, counts))


def ulps(got: np.ndarray, want: np.ndarray) -> float:
    """Largest distance of ``got`` from ``want`` in units of f32 ulp."""
    gap = np.spacing(np.abs(want).astype(np.float32))
    return float((np.abs(got - want) / gap).max())


def f32_bound(s: Smoke, x: np.ndarray) -> float:
    """Worst-case f32 error of a mean of at most K terms of |x|."""
    return float(s.k * np.finfo(np.float32).eps * np.abs(x).max())


def visit_share(cfg, events) -> float:
    """Share of the dense sweep's slot-block visits that the fold's
    hit-block grid makes on this round's drain schedule, before any
    shard split (``fold_block_visits``; the table modes fold the combined
    ``slot·K + client`` index into ``S·K`` rows).  A count, not a time."""
    from repro.core.engine_compiled import (TABLE_MODES,
                                            _combined_table_sched,
                                            demux_events)
    from repro.kernels.packet_scatter import fold_block_visits
    sched, _, _ = demux_events(cfg, events)
    n_rows = cfg.n_slots
    if cfg.agg_mode in TABLE_MODES:
        sched = _combined_table_sched(sched, cfg.n_clients)
        n_rows *= cfg.n_clients
    visited, dense = fold_block_visits(sched.idx, n_rows)
    return visited / dense


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def phase_line(phase, cfg, res, times, err, events, **extra):
    from repro.core.engine_compiled import host_pool_stats
    return dict(phase=phase, n_params=cfg.n_params, payload=cfg.payload,
                slots=cfg.n_slots, accepted=res.stats.data_enqueued,
                **times, max_err=err,
                fold_visit_share=visit_share(cfg, events),
                host_pool=host_pool_stats(), **extra)


def phase_a(s: Smoke):
    from repro.core.packets import PAYLOAD_F32
    cfg = s.cfg(PAYLOAD_F32)
    pk_int = packetize_np(s.ints, PAYLOAD_F32)
    ev_int, up = s.stream(pk_int)
    s.check_round(cfg, ev_int, s.prev_int)
    res, times = serve(cfg, s.ints, s.prev_int, ev_int)
    want, counts = mean_reference(pk_int, up, s.prev_int, s.n_params,
                                  divide=device_divide)
    got = np.asarray(res.new_global)
    check(res.stats.data_enqueued == int(up.sum()),
          "accepted packets != delivered (client, slot) pairs")
    check(np.array_equal(np.asarray(res.counts), counts),
          "phase a: per-slot counts differ from the reference")
    check(np.array_equal(got, want),
          f"phase a: integer round not bitwise equal to the reference "
          f"(max err {np.abs(got - want).max()})")
    int_ulps = ulps(got, mean_reference(pk_int, up, s.prev_int,
                                        s.n_params)[0])
    check(int_ulps <= 1, f"phase a: {int_ulps} ulps from numpy's mean")
    pk = packetize_np(s.normal, PAYLOAD_F32)
    ev, _ = s.stream(pk)
    res_n, _ = timed(cfg, s.normal, s.prev, ev)
    want_n, _ = mean_reference(pk.astype(np.float64), up, s.prev,
                               s.n_params)
    got_n = np.asarray(res_n.new_global)
    err = float(np.abs(got_n - want_n).max())
    bound = f32_bound(s, s.normal)
    check(err <= bound, f"phase a: error {err} above bound {bound}")
    emit(phase_line("a_exact_f32", cfg, res, times, err, ev_int,
                    bound=bound, int_bitwise=True,
                    int_ulps_vs_numpy_divide=int_ulps,
                    fallback_slots=int((counts == 0).sum())))
    return ev, got_n


def phase_b(s: Smoke, ev, exact_global):
    from repro.core.packets import PAYLOAD_F32
    cfg = s.cfg(PAYLOAD_F32, mode="approx")
    s.check_round(cfg, ev, s.prev)
    res, times = serve(cfg, s.normal, s.prev, ev)
    twin, _ = timed(s.cfg(PAYLOAD_F32, mode="approx", scan_body="jnp"),
                    s.normal, s.prev, ev)
    got = np.asarray(res.new_global)
    err = float(np.abs(got - np.asarray(twin.new_global)).max())
    check(err == 0.0 and np.array_equal(np.asarray(res.counts),
                                        np.asarray(twin.counts)),
          f"phase b: approx round not bitwise equal to its jnp twin "
          f"(max err {err})")
    rel = float(np.linalg.norm(got - exact_global)
                / np.linalg.norm(exact_global))
    emit(phase_line("b_approx_f32", cfg, res, times, err, ev,
                    twin_bitwise=True, rel_l2_vs_exact=rel))


def phase_c(s: Smoke):
    import jax.numpy as jnp
    from repro.core.packets import PAYLOAD_Q8, quantize_payload
    cfg = s.cfg(PAYLOAD_Q8)
    q, scales = quantize_payload(jnp.asarray(packetize_np(s.normal,
                                                          PAYLOAD_Q8)))
    q, scales = np.asarray(q), np.asarray(scales)
    ev_q8, _ = s.stream(q, scales=scales)
    s.check_round(cfg, ev_q8, s.prev)
    res, times = serve(cfg, s.normal, s.prev, ev_q8)
    decoded = q.astype(np.float32) * scales[..., None]
    ev_f32, _ = s.stream(decoded)
    want, _ = timed(cfg, s.normal, s.prev, ev_f32)
    got = np.asarray(res.new_global)
    err = float(np.abs(got - np.asarray(want.new_global)).max())
    check(err == 0.0 and np.array_equal(np.asarray(res.counts),
                                        np.asarray(want.counts)),
          f"phase c: q8 round not bitwise equal to the host-decoded "
          f"f32 round (max err {err})")
    emit(phase_line("c_exact_q8", cfg, res, times, err, ev_q8,
                    host_decoded_bitwise=True))


def phase_d(s: Smoke):
    from repro.core.packets import PAYLOAD_F32
    cfg = s.cfg(PAYLOAD_F32, agg_mode="trimmed_mean")
    pk = packetize_np(s.normal, PAYLOAD_F32)
    ev, up = s.stream(pk)
    s.check_round(cfg, ev, s.prev)
    res, times = serve(cfg, s.normal, s.prev, ev)
    want, m = trimmed_reference(pk, up, s.prev, s.n_params,
                                cfg.trim_beta)
    check(np.array_equal(np.asarray(res.counts), m.astype(np.float32)),
          "phase d: contributor counts differ from the reference")
    err = float(np.abs(np.asarray(res.new_global) - want).max())
    bound = f32_bound(s, s.normal)
    check(err <= bound, f"phase d: error {err} above bound {bound}")
    emit(phase_line("d_trimmed_mean_f32", cfg, res, times, err, ev,
                    bound=bound, trim_beta=cfg.trim_beta))


def phases_sharded(s: Smoke):
    """exact, f32 wire, integer payloads: ``shards=4`` and ``hosts=2,
    shards=2`` on real meshes, each bitwise equal to ``shards=1``."""
    import jax
    from repro.core.packets import PAYLOAD_F32
    from repro.runtime.sharding import host_ctx, worker_ctx
    pk = packetize_np(s.ints, PAYLOAD_F32)
    ev, _ = s.stream(pk)
    base_cfg = s.cfg(PAYLOAD_F32)
    s.check_round(base_cfg, ev, s.prev_int)
    base, times = serve(base_cfg, s.ints, s.prev_int, ev)
    emit(phase_line("shards_1", base_cfg, base, times, 0.0, ev))
    layouts = [("shards_4", dict(shards=4), worker_ctx(4), (4,)),
               ("hosts_2_shards_2", dict(hosts=2, shards=2),
                host_ctx(2, 2), (2, 2))]
    for name, kw, ctx, shape in layouts:
        check(ctx is not None and ctx.mesh.devices.shape == shape
              and all(d.platform == jax.devices()[0].platform
                      for d in ctx.mesh.devices.flat),
              f"{name}: no real {shape} mesh")
        cfg = s.cfg(PAYLOAD_F32, **kw)
        s.check_round(cfg, ev, s.prev_int, collective=True)
        res, times = serve(cfg, s.ints, s.prev_int, ev)
        same = (np.array_equal(np.asarray(res.new_global),
                               np.asarray(base.new_global))
                and np.array_equal(np.asarray(res.counts),
                                   np.asarray(base.counts)))
        check(same, f"{name}: not bitwise equal to shards=1")
        emit(phase_line(name, cfg, res, times, 0.0, ev, mesh=list(shape),
                        bitwise_vs_shards_1=True))


def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded rounds on a 4-chip host")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    from repro.configs.paper_cnn import CONFIG
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    emit(dict(phase="setup", cache_dir=cache,
              cache_entries=cache_entries(cache)))
    s = Smoke(seed=args.seed, cnn=CONFIG)
    if args.chips == 4:
        phases_sharded(s)
    else:
        ev, exact_global = phase_a(s)
        phase_b(s, ev, exact_global)
        phase_c(s)
        phase_d(s)
    emit(dict(ok=True, device=dict(platform=devices[0].platform,
                                   kind=devices[0].device_kind,
                                   count=len(devices))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
