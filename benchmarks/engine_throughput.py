"""Eager vs compiled packet-path engine throughput (ISSUE 3 acceptance).

Sweeps K ∈ {10, 64, 256} × {exact, approx} × {eager-engine,
compiled-engine} through one full server round — identical streams,
identical ring topology — and reports packets/sec and round latency.
The eager engine pays one Python-dispatched device call per drained
ring; the compiled engine demuxes the stream into a dense drain
schedule on the host and runs the whole round as ONE jitted
``lax.scan`` with the END divide and TX downlink fused in
(core/engine_compiled.py, DESIGN.md §3).  ``compiled_overlap`` rows
amortize ``run_compiled_rounds`` over several rounds, so round r+1's
host demux hides under round r's device scan.

Measurements reuse the memoized ``engine_measured.measure_engine_round``
caches, so running under ``benchmarks/run.py`` (after fig6/fig7) adds
only the K > 10 configurations.

``compiled_q8`` rows run the compressed int8 uplink (DESIGN.md §9):
the same stream quantized to int8 payloads + per-packet scales, the
dequantize fused into the compiled drain scan.  Every row additionally
reports wire economics — ``payload_bytes``/``packet_wire_bytes`` per
packet, achieved ``wire_mb_s``, and ``bytes_per_model_delta`` (wire
bytes to ship one client's full model update) — plus the
``WIRE_BUDGET_MB_S``-capped ``effective_pkts_per_s``: on a NIC whose
uplink budget, not the server, is the bottleneck, the q8 rows' measured
``speedup_at_wire_budget`` is the ~2.4x admission-rate win of the
smaller wire format (EXPERIMENTS.md §Compressed-uplink).

``compiled_async`` rows run the async buffered engine (DESIGN.md §10):
several waves' worth of complete sessions stream through ONE
``buffer_size=K`` demux call, so every wave emits once and the whole
multi-wave fold is a single donated device dispatch.  Like the shard
rows, the timed stage is the device dispatch (the host demux is pure
host code a double-buffered driver overlaps; it is reported separately
as ``demux_s``).

Each run overwrites ``BENCH_engine.json`` (committed — its git history
is the perf trajectory across PRs; schema in EXPERIMENTS.md
§Engine-throughput).

``--shard-sweep`` instead sweeps the sharded round engine
(``EngineConfig(shards=N)``, DESIGN.md §7) over shards ∈ {1, 2, 4, 8}
at K=256 on the ``'worker'`` device mesh and writes
``BENCH_shard.json`` (schema in EXPERIMENTS.md §Shard-scaling).  The
timed stage is one sharded round dispatch (per-shard schedule split +
transfer + the compiled drain scan the sharding parallelizes); the
event demux is identical across shard counts and is reported
separately (the overlap driver hides it under the previous round's
scan).  Run it with 8 devices, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/engine_throughput.py --shard-sweep

``--host-sweep`` sweeps the hierarchical engine
(``EngineConfig(hosts=H, shards=S)``, DESIGN.md §12) over the
(hosts, shards) grid and appends ``engine="compiled_hier"`` rows to the
same ``BENCH_shard.json`` (combine both flags for the full file;
schema in EXPERIMENTS.md §Host-sweep).

Usage:
    python benchmarks/engine_throughput.py [--quick] [--shard-sweep]
                                           [--host-sweep]
                                           [--out BENCH_engine.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

CLIENT_SWEEP = (10, 64, 256)
# defaults match engine_measured.measure_engine_round so fig6/fig7 and
# this sweep share one warm, memoized measurement per configuration
N_PARAMS, PAYLOAD, RING_CAPACITY = 16384, 64, 64
LOSS_RATE, DUP_RATE = 0.01, 0.02
OVERLAP_ROUNDS = 4
SHARD_SWEEP = (1, 2, 4, 8)
SHARD_K = 256               # the worker-scaling point (paper Fig. 6/7)
SHARD_WORKERS = 8           # rings == BlueField-2 cores; fixed across the
                            # sweep so batching (and bits) never change
HOST_SWEEP = ((1, 1), (1, 2), (2, 1), (2, 2), (4, 1), (4, 2))
                            # (hosts, shards) grid for --host-sweep
                            # (DESIGN.md §12); quick trims hosts to {1,2}
# Simulated NIC uplink budget for the wire-limited columns.  Chosen so
# the wire, not the server, is the bottleneck for BOTH formats on every
# compiled row (f32 admits ~37k pkts/s, q8 ~87k — the compiled engine
# sustains >100k), so ``speedup_at_wire_budget`` measures the format,
# not the machine.
WIRE_BUDGET_MB_S = 12.0


def _wire_cols(row, wire: str = "f32"):
    """Attach the wire-economics columns every row carries (§9)."""
    from repro.core.packets import packet_wire_bytes, payload_wire_bytes
    pw = packet_wire_bytes(row["payload"], wire)
    n_slots = -(-row["n_params"] // row["payload"])
    row["wire_dtype"] = wire
    row["payload_bytes"] = payload_wire_bytes(row["payload"], wire)
    row["packet_wire_bytes"] = pw
    row["wire_mb_s"] = row["pkts_per_s"] * pw / 1e6
    row["bytes_per_model_delta"] = pw * n_slots
    row["wire_limited_pkts_per_s"] = WIRE_BUDGET_MB_S * 1e6 / pw
    row["effective_pkts_per_s"] = min(row["pkts_per_s"],
                                      row["wire_limited_pkts_per_s"])
    return row


def _measure_q8_round(mode: str, n_clients: int, n_params: int,
                      iters: int = 3):
    """Compiled round on the q8 wire: int8 schedule + scale column,
    dequantize fused into the drain scan.  Mirrors
    ``engine_measured.measure_engine_round``'s compiled branch (same
    seed, warmup, min-of-iters) so the f32/q8 delta is the wire format,
    not the harness."""
    from repro.core import engine_compiled as ec
    from repro.core.aggregation import quantize_packets
    from repro.core.packets import packetize
    from repro.core.server import EngineConfig, make_uplink_stream

    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(n_clients, n_params))
                        .astype(np.float32))
    prev = jnp.zeros((n_params,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, PAYLOAD))(flats)
    q, scales = quantize_packets(pk)
    events, _ = make_uplink_stream(rng, q, loss_rate=LOSS_RATE,
                                   dup_rate=DUP_RATE, scales=scales)
    down = jnp.asarray((rng.random((n_clients, pk.shape[1])) > LOSS_RATE)
                       .astype(np.float32))
    cfg = EngineConfig(n_clients=n_clients, n_params=n_params,
                       payload=PAYLOAD, ring_capacity=RING_CAPACITY,
                       mode=mode, compile=True)
    stats = {}

    def one_round():
        t0 = time.perf_counter()
        sched, st, _ = ec.demux_events(cfg, events)
        total = jnp.zeros((cfg.n_slots, PAYLOAD), jnp.float32)
        counts = jnp.zeros((cfg.n_slots,), jnp.float32)
        _, _, new_global, new_flats = ec.dispatch_round(
            cfg, sched, total, counts, prev, client_flats=flats,
            down_mask=down)
        new_flats.block_until_ready()
        stats["packets"] = float(st.data_enqueued)
        return time.perf_counter() - t0

    one_round()                                       # warmup: jit trace
    dt = min(one_round() for _ in range(iters))
    return {"response_time": dt, **stats}


def _measure_async(mode: str, n_clients: int, n_params: int,
                   waves: int = OVERLAP_ROUNDS, iters: int = 3):
    """Async buffered engine (DESIGN.md §10): ``waves`` rounds' worth of
    complete sessions stream through ONE ``buffer_size=K`` demux call —
    every wave emits once, and the whole multi-wave fold is a single
    donated device dispatch (a ``lax.scan`` over emit windows).

    As in ``shard_rows``, the timed stage is the device dispatch
    (``scan_s``); the host demux is reported separately (``demux_s``) —
    it is pure host code with no device dependency, so a double-buffered
    driver hides wave t+1's demux under wave t's scan exactly like the
    sync ``compiled_overlap`` rows.  Both are returned; the row's
    ``pkts_per_s`` is the dispatch rate, ``round_s`` the unoverlapped
    per-wave total."""
    from repro.core import engine_compiled as ec
    from repro.core.packets import packetize
    from repro.core.server import EngineConfig

    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(n_clients, n_params))
                        .astype(np.float32))
    prev = jnp.zeros((n_params,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, PAYLOAD))(flats)
    from repro.core.server import make_uplink_stream
    events = []
    for t in range(waves):
        ev, _ = make_uplink_stream(np.random.default_rng(t), pk,
                                   loss_rate=LOSS_RATE, dup_rate=DUP_RATE)
        events += ev
    cfg = EngineConfig(n_clients=n_clients, n_params=n_params,
                       payload=PAYLOAD, ring_capacity=RING_CAPACITY,
                       mode=mode, compile=True, buffer_size=n_clients)
    t0 = time.perf_counter()
    asched, st, _ = ec.demux_events_async(cfg, events)
    demux_s = (time.perf_counter() - t0) / waves
    assert asched.n_emits == waves

    def one():
        total = jnp.zeros((cfg.n_slots, PAYLOAD), jnp.float32)
        counts = jnp.zeros((cfg.n_slots,), jnp.float32)
        t0 = time.perf_counter()
        _, _, g, _, _ = ec.dispatch_async(cfg, asched, total, counts, prev)
        g.block_until_ready()
        return (time.perf_counter() - t0) / waves

    one()                                             # warmup: jit trace
    scan_s = min(one() for _ in range(iters))
    return {"response_time": scan_s,
            "packets": float(st.data_enqueued) / waves,
            "demux_s": demux_s, "scan_s": scan_s,
            "round_s": demux_s + scan_s,
            "buffer_size": n_clients, "waves": waves}


def _measure_overlap(mode: str, n_clients: int, n_params: int,
                     rounds: int = OVERLAP_ROUNDS):
    """Amortized per-round time of the double-buffered driver."""
    from repro.core import engine_compiled as ec
    from repro.core.packets import packetize
    from repro.core.server import EngineConfig, make_uplink_stream

    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(n_clients, n_params))
                        .astype(np.float32))
    prev = jnp.zeros((n_params,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, PAYLOAD))(flats)
    events, _ = make_uplink_stream(rng, pk, loss_rate=LOSS_RATE,
                                   dup_rate=DUP_RATE)
    down = jnp.asarray((rng.random((n_clients, pk.shape[1])) > LOSS_RATE)
                       .astype(np.float32))
    cfg = EngineConfig(n_clients=n_clients, n_params=n_params,
                       payload=PAYLOAD, ring_capacity=RING_CAPACITY,
                       mode=mode, compile=True)
    stream = [(events, flats, down)] * rounds
    ec.run_compiled_rounds(cfg, stream, prev)          # warmup
    t0 = time.perf_counter()
    results = ec.run_compiled_rounds(cfg, stream, prev)
    dt = (time.perf_counter() - t0) / rounds
    return {"response_time": dt,
            "packets": float(results[0].stats.data_enqueued)}


def rows(ks=CLIENT_SWEEP, quick: bool = False):
    try:                                  # package context (run.py, -m)
        from benchmarks.engine_measured import measure_engine_round
    except ImportError:                   # standalone: script dir on sys.path
        from engine_measured import measure_engine_round
    n_params = 4096 if quick else N_PARAMS
    out = []
    for k in ks:
        for mode in ("exact", "approx"):
            # kwarg names/order must match measured_rows exactly —
            # lru_cache keys on the literal signature (K=10 full-size
            # rows then reuse fig6/fig7's warm measurement)
            eager = measure_engine_round(
                mode=mode, n_clients=k, n_params=n_params, compiled=False)
            comp = measure_engine_round(
                mode=mode, n_clients=k, n_params=n_params, compiled=True)
            q8 = _measure_q8_round(mode, k, n_params)
            variants = [("eager", eager), ("compiled", comp),
                        ("compiled_q8", q8)]
            if not quick:
                variants.append(
                    ("compiled_overlap", _measure_overlap(mode, k, n_params)))
            variants.append(
                ("compiled_async",
                 _measure_async(mode, k, n_params,
                                waves=2 if quick else OVERLAP_ROUNDS)))
            comp_row = None
            for engine, m in variants:
                t = m["response_time"]
                row = {
                    "k": k, "mode": mode, "engine": engine,
                    "n_params": n_params, "payload": PAYLOAD,
                    "ring_capacity": RING_CAPACITY,
                    "packets": m["packets"],
                    "round_s": t,
                    "pkts_per_s": m["packets"] / t,
                    "interpret": jax.default_backend() != "tpu",
                }
                _wire_cols(row, "q8" if engine == "compiled_q8" else "f32")
                if engine == "compiled_async":
                    # buffer_size=K: one emit per wave; pkts_per_s is
                    # the dispatch rate, round_s the unoverlapped total
                    for key in ("demux_s", "scan_s", "round_s",
                                "buffer_size", "waves"):
                        row[key] = m[key]
                if engine != "eager":
                    row["speedup_vs_eager"] = (eager["response_time"] / t)
                if engine == "compiled":
                    comp_row = row
                tag = f" ({row['speedup_vs_eager']:6.1f}x vs eager)" \
                    if engine != "eager" else ""
                if engine == "compiled_q8":
                    # the headline: packets admitted per second when the
                    # simulated NIC uplink budget is the bottleneck
                    row["speedup_at_wire_budget"] = (
                        row["effective_pkts_per_s"]
                        / comp_row["effective_pkts_per_s"])
                    tag += (f" [{row['speedup_at_wire_budget']:.2f}x @ "
                            f"{WIRE_BUDGET_MB_S:.0f} MB/s wire]")
                out.append(row)
                print(f"K={k:4d} {mode:6s}/{engine:16s} "
                      f"{t*1e3:10.2f} ms/round "
                      f"{row['pkts_per_s']/1e3:10.1f} kpkt/s "
                      f"{row['wire_mb_s']:7.1f} MB/s{tag}")
    return out


def shard_rows(quick: bool = False):
    """Sharded-engine sweep: shards ∈ SHARD_SWEEP at the K=256 scaling
    point (quick: K=64, small rounds, exact only — the CI smoke)."""
    from repro.core import engine_compiled as ec
    from repro.core.packets import packetize
    from repro.core.server import EngineConfig, make_uplink_stream
    from repro.runtime.sharding import worker_mesh

    k = 64 if quick else SHARD_K
    n_params = 4096 if quick else N_PARAMS
    modes = ("exact",) if quick else ("exact", "approx")
    # quick rounds scan in single-digit ms, where cross-device dispatch
    # jitter swamps a one-shot timing (±40% run-to-run observed); time a
    # burst of dispatches per sample so the bench_gate threshold gates
    # the code, not the scheduler
    reps = 8 if quick else 1
    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(k, n_params)).astype(np.float32))
    prev = jnp.zeros((n_params,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, PAYLOAD))(flats)
    events, _ = make_uplink_stream(rng, pk, loss_rate=LOSS_RATE,
                                   dup_rate=DUP_RATE)
    out = []
    # the drain schedule is shard- and mode-independent (it depends only
    # on the stream and the ring topology): demux once, reuse everywhere
    # — dispatch_round re-demuxes it per shard internally
    cfg0 = EngineConfig(n_clients=k, n_params=n_params, payload=PAYLOAD,
                        ring_capacity=RING_CAPACITY,
                        n_workers=SHARD_WORKERS, compile=True)
    t0 = time.perf_counter()
    sched, st, _ = ec.demux_events(cfg0, events)
    demux_s = time.perf_counter() - t0
    for mode in modes:
        base_scan = None
        for shards in SHARD_SWEEP:
            cfg = EngineConfig(n_clients=k, n_params=n_params,
                               payload=PAYLOAD, ring_capacity=RING_CAPACITY,
                               n_workers=SHARD_WORKERS, mode=mode,
                               compile=True, shards=shards)

            def one():
                t0 = time.perf_counter()
                for _ in range(reps):
                    total = jnp.zeros((cfg.n_slots, PAYLOAD), jnp.float32)
                    counts = jnp.zeros((cfg.n_slots,), jnp.float32)
                    _, _, new_global, _ = ec.dispatch_round(
                        cfg, sched, total, counts, prev)
                    new_global.block_until_ready()
                return (time.perf_counter() - t0) / reps

            one()                                     # warmup: jit trace
            scan_s = min(one() for _ in range(3))
            base_scan = scan_s if shards == 1 else base_scan
            row = {
                "k": k, "mode": mode, "engine": "compiled_shard",
                "shards": shards,
                "on_mesh": worker_mesh(shards) is not None,
                "n_params": n_params, "payload": PAYLOAD,
                "ring_capacity": RING_CAPACITY,
                "n_workers": SHARD_WORKERS,
                "packets": float(st.data_enqueued),
                "demux_s": demux_s,
                "scan_s": scan_s,
                "round_s": demux_s + scan_s,
                "pkts_per_s": st.data_enqueued / scan_s,
                "speedup_vs_shard1": base_scan / scan_s,
                "interpret": jax.default_backend() != "tpu",
            }
            _wire_cols(row)
            out.append(row)
            print(f"K={k:4d} {mode:6s}/shards={shards} "
                  f"{'mesh' if row['on_mesh'] else 'emul'} "
                  f"{scan_s*1e3:9.2f} ms/scan "
                  f"{row['pkts_per_s']/1e3:9.1f} kpkt/s "
                  f"({row['speedup_vs_shard1']:4.2f}x vs 1 shard)")
    return out


def host_rows(quick: bool = False):
    """Hierarchical-engine sweep: (hosts, shards) ∈ HOST_SWEEP at the
    K=256 scaling point (quick: K=64, hosts ≤ 2, exact only — the CI
    smoke).  Schema in EXPERIMENTS.md §Host-sweep.

    The timed stage is one hierarchical round dispatch: the per-host
    arrival partition + per-host ring demux + shard split + the
    two-level psum fold (DESIGN.md §12).  Unlike ``shard_rows`` the
    host split is part of the timed stage — a real deployment demuxes
    per host in parallel on the hosts themselves, so the single-machine
    row is an upper bound on the partition cost, not an estimate of
    cross-machine latency (the emulated-multi-process caveat,
    EXPERIMENTS.md §Host-sweep).
    """
    from repro.core import engine_compiled as ec
    from repro.core.packets import packetize
    from repro.core.server import EngineConfig, make_uplink_stream
    from repro.runtime.sharding import host_worker_mesh, worker_mesh

    k = 64 if quick else SHARD_K
    n_params = 4096 if quick else N_PARAMS
    modes = ("exact",) if quick else ("exact", "approx")
    combos = tuple((h, s) for h, s in HOST_SWEEP if not quick or h <= 2)
    # same burst-timing rationale as shard_rows: quick rounds scan in
    # single-digit ms where dispatch jitter swamps one-shot samples
    reps = 8 if quick else 1
    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.normal(size=(k, n_params)).astype(np.float32))
    prev = jnp.zeros((n_params,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, PAYLOAD))(flats)
    events, _ = make_uplink_stream(rng, pk, loss_rate=LOSS_RATE,
                                   dup_rate=DUP_RATE)
    out = []
    # one global demux shared by every row: the accepted-arrival stream
    # is host-count independent; dispatch_round re-partitions it per
    # (hosts, shards) internally
    cfg0 = EngineConfig(n_clients=k, n_params=n_params, payload=PAYLOAD,
                        ring_capacity=RING_CAPACITY,
                        n_workers=SHARD_WORKERS, compile=True)
    t0 = time.perf_counter()
    sched, st, _ = ec.demux_events(cfg0, events)
    demux_s = time.perf_counter() - t0
    for mode in modes:
        base = {}
        for hosts, shards in combos:
            cfg = EngineConfig(n_clients=k, n_params=n_params,
                               payload=PAYLOAD, ring_capacity=RING_CAPACITY,
                               n_workers=SHARD_WORKERS, mode=mode,
                               compile=True, hosts=hosts, shards=shards)

            def one():
                t0 = time.perf_counter()
                for _ in range(reps):
                    total = jnp.zeros((cfg.n_slots, PAYLOAD), jnp.float32)
                    counts = jnp.zeros((cfg.n_slots,), jnp.float32)
                    _, _, new_global, _ = ec.dispatch_round(
                        cfg, sched, total, counts, prev)
                    new_global.block_until_ready()
                return (time.perf_counter() - t0) / reps

            one()                                     # warmup: jit trace
            scan_s = min(one() for _ in range(3))
            if hosts == 1:
                base[shards] = scan_s
            row = {
                "k": k, "mode": mode, "engine": "compiled_hier",
                "hosts": hosts, "shards": shards,
                # hosts=1 rows run the flat engine (1-D worker mesh);
                # hosts>1 rows run the 2-D ('host','worker') mesh.
                "on_mesh": (host_worker_mesh(hosts, shards) is not None
                            if hosts > 1 else
                            worker_mesh(shards) is not None),
                "n_params": n_params, "payload": PAYLOAD,
                "ring_capacity": RING_CAPACITY,
                "n_workers": SHARD_WORKERS,
                "packets": float(st.data_enqueued),
                "demux_s": demux_s,
                "scan_s": scan_s,
                "round_s": demux_s + scan_s,
                "pkts_per_s": st.data_enqueued / scan_s,
                "speedup_vs_host1": base[shards] / scan_s,
                "interpret": jax.default_backend() != "tpu",
            }
            _wire_cols(row)
            out.append(row)
            print(f"K={k:4d} {mode:6s}/hosts={hosts} shards={shards} "
                  f"{'mesh' if row['on_mesh'] else 'emul'} "
                  f"{scan_s*1e3:9.2f} ms/scan "
                  f"{row['pkts_per_s']/1e3:9.1f} kpkt/s "
                  f"({row['speedup_vs_host1']:4.2f}x vs 1 host)")
    return out


def main():
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small rounds, K<=64, no overlap rows (CI smoke)")
    ap.add_argument("--shard-sweep", action="store_true",
                    help="sweep EngineConfig(shards=N) over the worker "
                         "mesh and write BENCH_shard.json instead")
    ap.add_argument("--host-sweep", action="store_true",
                    help="sweep EngineConfig(hosts=H, shards=S) over the "
                         "(host, worker) mesh; rows join BENCH_shard.json "
                         "(combine with --shard-sweep for both families)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.shard_sweep or args.host_sweep:
        out_path = args.out or os.path.join(root, "BENCH_shard.json")
        rws = []
        if args.shard_sweep:
            rws += shard_rows(quick=args.quick)
        if args.host_sweep:
            rws += host_rows(quick=args.quick)
        result = {
            "bench": "shard_scaling",
            "backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "quick": args.quick,
            "shard_sweep": list(SHARD_SWEEP),
            "host_sweep": list(list(c) for c in HOST_SWEEP),
            "payload": PAYLOAD,
            "ring_capacity": RING_CAPACITY,
            "n_workers": SHARD_WORKERS,
            "loss_rate": LOSS_RATE,
            "dup_rate": DUP_RATE,
            "rows": rws,
        }
    else:
        out_path = args.out or os.path.join(root, "BENCH_engine.json")
        ks = (10, 64) if args.quick else CLIENT_SWEEP
        result = {
            "bench": "engine_throughput",
            "backend": jax.default_backend(),
            "quick": args.quick,
            "client_sweep": list(ks),
            "payload": PAYLOAD,
            "ring_capacity": RING_CAPACITY,
            "loss_rate": LOSS_RATE,
            "dup_rate": DUP_RATE,
            "wire_budget_mb_s": WIRE_BUDGET_MB_S,
            "rows": rows(ks=ks, quick=args.quick),
        }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {out_path} ({len(result['rows'])} rows)")


if __name__ == "__main__":
    main()
