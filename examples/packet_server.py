"""The packet-path server engine in 30 seconds on CPU.

Builds one round of the paper's uplink as a real packet stream — lossy,
out-of-order, duplicated, framed by START/END control packets — and
drives it through the ring-buffered RX → worker → TX engine
(core/server.py) twice: once as the locked (exact) server and once as
the lock-free (approximate) server whose racing adds are dropped
last-writer-wins.  Prints the pipeline stats and verifies the exact
round is bitwise identical to the one-shot ``fused_round_step``.

``--compile`` routes the identical rounds through the compiled engine
(core/engine_compiled.py): a vectorized demux pass plus ONE jitted
``lax.scan`` per round with donated accumulators — same bits, no
per-drain dispatch (DESIGN.md §3).

``--shards N`` additionally demuxes the compiled drain schedule over N
worker-mesh shards, each folding a per-shard partial sum combined at
END — the paper's per-core layout (DESIGN.md §7), still bitwise
identical.  On the CPU, with fewer than N devices a single-device
emulation runs (an accelerator with too few devices is an error); to
see the real mesh:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--hosts N`` runs the hierarchical engine (DESIGN.md §12): clients
are split into N contiguous ownership ranges, each "host" demuxes only
its own sessions into shard-local partials, and one psum per mesh
level (worker within a host, then host across hosts) produces the
global — verified bitwise against both the flat compiled round and the
eager per-host twin ``run_hier_round``.  Composes with ``--shards``;
on the CPU, with fewer than N*shards devices a single-device emulation
runs.

``--deadline [N]`` makes client 0 a permanent straggler: its last
packets and its END trail the round deadline, the server times it out
and closes on whatever arrived (DESIGN.md §8) — and the demo verifies
the partial round is *bitwise identical* to the same round with the
straggler's undelivered packets as wire losses.  Without N the deadline
lands right after the healthy clients' ENDs.

``--churn`` runs a short multi-round demo through the churn driver
(core/rounds.py): per-round Bernoulli client sampling, join/leave
membership churn, and mid-upload stragglers timed out at the close.

``--int8`` sends the same round over the compressed uplink (DESIGN.md
§9): int8 payloads + a per-packet scale in the header, ~3.8x fewer
payload bytes on the wire, the dequantize fused into the compiled
drain scan — and verifies the q8 round is *bitwise identical* to
decoding the wire bytes on the host and running the f32 engine.

``--attack MODEL --agg MODE`` runs the Byzantine demo (DESIGN.md §11):
the same lossy round with MODEL poisoners on the wire (``sign_flip``,
``scale``, ``nan``) served twice — once through the plain mean, once
through the robust finalize (``trimmed_mean`` / ``median`` /
``norm_clip``) — printing each global's error against the honest mean,
and verifying the robust round is *bitwise identical* between the
eager table engine and the compiled combined-index fold.  NaN
poisoners exercise the malformed wire guard instead: the packets are
dropped and counted before any accumulator sees them.

``--async [B]`` kills the round barrier entirely (DESIGN.md §10):
client sessions interleave freely across waves, the server folds each
update at its END and emits a new staleness-weighted global every B
accepted updates — and the demo verifies the compiled one-scan fold is
*bitwise identical* to the eager per-packet fold at every emitted
global (composable with ``--shards``).

Run:  PYTHONPATH=src python examples/packet_server.py [--compile]
        [--shards N] [--hosts N] [--deadline [N]] [--churn] [--int8]
        [--async [B]] [--attack MODEL] [--agg MODE]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import fused_round_step
from repro.core.packets import packetize
from repro.core.rounds import losses_only_twin, make_straggler_stream
from repro.core.server import (EngineConfig, make_uplink_stream,
                               run_engine_round)
from repro.runtime.compile_cache import enable_compile_cache


def straggler_demo(args):
    """Deadline-closed partial round: a permanent straggler is timed
    out and the round stays bitwise equal to its losses-only twin."""
    K, P, W = 10, 4096, 64
    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.integers(-8, 9, (K, P)).astype(np.float32))
    prev = jnp.zeros((P,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.0468,
                                   dup_rate=0.05)
    # client 0 delivers only its first 20 surviving packets, then
    # stalls: the rest of its DATA and its END trail the deadline
    # (core/rounds.py owns the stream rearrangement)
    dl_events, auto_deadline, loss_events = make_straggler_stream(
        events, straggler=0, keep=20)
    deadline = auto_deadline if args.deadline < 0 else args.deadline
    if args.deadline >= 0:
        # an explicit deadline cuts at an arbitrary position: derive
        # the matching twin from the same single authority
        loss_events = losses_only_twin(dl_events, deadline)
    print(f"\n== deadline-closed partial round (deadline={deadline}, "
          f"straggler=client 0) ==")
    for mode in ("exact", "approx"):
        kw = dict(n_clients=K, n_params=P, payload=W, ring_capacity=64,
                  mode=mode, compile=args.compile, shards=args.shards)
        got = run_engine_round(
            EngineConfig(round_deadline=deadline, **kw), flats, prev,
            dl_events)
        want = run_engine_round(EngineConfig(**kw), flats, prev,
                                loss_events)
        same = (np.array_equal(np.asarray(got.new_global),
                               np.asarray(want.new_global))
                and np.array_equal(np.asarray(got.counts),
                                   np.asarray(want.counts)))
        s = got.stats
        print(f"  {mode:6s}: {s.stragglers_timed_out} straggler timed "
              f"out, {s.late_dropped} late packets dropped, "
              f"{s.data_enqueued} aggregated; bitwise == losses-only "
              f"round: {same}")
        assert same, "deadline round diverged from its losses-only twin"


def churn_demo(args):
    """Multi-round serving loop: sampling + churn + stragglers."""
    from repro.core.rounds import ChurnConfig, run_churn_rounds
    K, P, W = 10, 4096, 64
    rng = np.random.default_rng(0)
    flats = jnp.asarray(rng.integers(-8, 9, (K, P)).astype(np.float32))
    cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                       ring_capacity=64, compile=True,
                       shards=args.shards)
    churn = ChurnConfig(participation=0.7, p_join=0.3, p_leave=0.1,
                        straggle_rate=0.25, loss_rate=0.0468,
                        dup_rate=0.05, down_loss_rate=0.0468)
    print(f"\n== churn driver: 5 rounds, 70% participation, 25% "
          f"straggle, join/leave churn ==")
    hist = run_churn_rounds(cfg, churn, flats, jnp.zeros((P,)), 5,
                            rng=rng)
    for r, (res, log) in enumerate(zip(hist.results, hist.logs)):
        s = res.stats
        print(f"  round {r}: {int(log.selected.sum())} sampled "
              f"({int(log.stragglers.sum())} straggled, "
              f"{int(log.active.sum())}/{K} active), "
              f"{s.data_enqueued} pkts aggregated, "
              f"{s.stragglers_timed_out} timed out at close, "
              f"{int(jnp.sum(res.counts > 0))}/{res.counts.shape[0]} "
              f"slots delivered")


def int8_demo(args):
    """Compressed uplink: int8 wire payloads, fused dequantize, bitwise
    equal to host-decoding the same bytes and running the f32 engine."""
    from repro.core.aggregation import quantize_packets
    from repro.core.packets import packet_wire_bytes
    K, P, W = 10, 4096, 64
    rng = np.random.default_rng(0)
    client_flats = jnp.asarray(rng.normal(size=(K, P))
                               .astype(np.float32))
    prev = jnp.zeros((P,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(client_flats)
    q, scales = quantize_packets(pk)
    # same rng seed => identical loss/dup/ordering fate on both wires
    ev_q8, _ = make_uplink_stream(np.random.default_rng(1), q,
                                  loss_rate=0.0468, dup_rate=0.05,
                                  scales=scales)
    deq = (np.asarray(q).astype(np.float32)
           * np.asarray(scales, np.float32)[..., None])
    ev_f32, _ = make_uplink_stream(np.random.default_rng(1),
                                   jnp.asarray(deq),
                                   loss_rate=0.0468, dup_rate=0.05)
    n_data = len(ev_q8) - 2 * K
    b_q8 = n_data * packet_wire_bytes(W, "q8")
    b_f32 = n_data * packet_wire_bytes(W, "f32")
    print(f"\n== compressed int8 uplink (DESIGN.md §9) ==")
    print(f"  {n_data} DATA packets on the wire: "
          f"{b_f32/1e3:.0f} kB as f32 -> {b_q8/1e3:.0f} kB as q8 "
          f"({b_f32/b_q8:.2f}x smaller)")
    for mode in ("exact", "approx"):
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=64, mode=mode,
                           compile=args.compile, shards=args.shards)
        got = run_engine_round(cfg, client_flats, prev, ev_q8)
        want = run_engine_round(cfg, client_flats, prev, ev_f32)
        same = (np.array_equal(np.asarray(got.new_global),
                               np.asarray(want.new_global))
                and np.array_equal(np.asarray(got.counts),
                                   np.asarray(want.counts)))
        print(f"  {mode:6s}: {got.stats.data_enqueued} pkts aggregated, "
              f"fused dequant bitwise == host-decoded f32 round: {same}")
        assert same, "q8 round diverged from its host-decoded twin"


def attack_demo(args):
    """Byzantine-robust aggregation demo (DESIGN.md §11): the same
    poisoned round served through the plain mean and through the robust
    finalize, with the eager-vs-compiled bitwise check on both."""
    from repro.core.rounds import AttackConfig, apply_attack

    K, P, W = 10, 4096, 64
    f = 2                                  # Byzantine clients
    rng = np.random.default_rng(0)
    # positive-valued honest updates: a sign-flip is then a genuine
    # coordinate-wise outlier (on zero-symmetric data a flipped update
    # is distributed like an honest one and nothing can tell them apart)
    flats = jnp.asarray(rng.integers(1, 9, (K, P)).astype(np.float32))
    prev = jnp.zeros((P,), jnp.float32)
    pk = jax.vmap(lambda fl: packetize(fl, W))(flats)
    att = AttackConfig(model=args.attack, n_attackers=f, boost=1e3,
                       nan_rate=0.25)
    pk_att = apply_attack(rng, pk, att)
    events, _ = make_uplink_stream(rng, pk_att, loss_rate=0.0468,
                                   dup_rate=0.05)
    honest = np.asarray(flats).mean(axis=0)
    hnorm = np.linalg.norm(honest)
    print(f"\n== Byzantine round: {f}/{K} x {args.attack} attackers, "
          f"agg_mode={args.agg} (DESIGN.md §11) ==")
    for agg in ("mean", args.agg):
        kw = dict(n_clients=K, n_params=P, payload=W, ring_capacity=64,
                  agg_mode=agg, trim_beta=0.25, clip_tau=50.0)
        re_ = run_engine_round(EngineConfig(**kw), flats, prev, events)
        rc = run_engine_round(EngineConfig(**kw, compile=True,
                                           shards=args.shards),
                              flats, prev, events)
        same = (np.array_equal(np.asarray(re_.new_global),
                               np.asarray(rc.new_global))
                and np.array_equal(np.asarray(re_.counts),
                                   np.asarray(rc.counts))
                and re_.stats == rc.stats)
        err = float(np.linalg.norm(np.asarray(rc.new_global) - honest)
                    / hnorm)
        s = rc.stats
        extra = (f", {s.malformed_dropped} malformed dropped at the "
                 f"wire" if s.malformed_dropped else "")
        print(f"  {agg:12s}: global error vs honest mean = {err:9.3f}"
              f"{extra}; eager == compiled bitwise: {same}")
        assert same, f"{agg} round diverged between eager and compiled"
        assert np.isfinite(np.asarray(rc.new_global)).all(), \
            "non-finite global escaped the wire guard"


def async_demo(args):
    """Async buffered mode (DESIGN.md §10): no round barrier — sessions
    interleave across waves, the server emits a new global every B
    folded updates, stale updates are down-weighted, and the compiled
    scan fold is bitwise the eager per-packet fold."""
    from repro.core.rounds import make_async_stream
    from repro.core.server import run_async_engine
    K, P, W = 10, 4096, 64
    B = args.async_b
    rng = np.random.default_rng(0)
    events = []
    for t in range(3):
        flats = jnp.asarray(rng.integers(-8, 9, (K, P))
                            .astype(np.float32))
        pk = jax.vmap(lambda f: packetize(f, W))(flats)
        sel = rng.random(K) < 0.8          # participation churn
        open_ = rng.random(K) < 0.15       # sessions left in flight
        ver = rng.integers(0, 3, K)        # version-at-send tags
        ev, _ = make_async_stream(rng, pk, sel, ver, open_sessions=open_,
                                  loss_rate=0.0468, dup_rate=0.05)
        events += ev
    print(f"\n== async buffered mode (B={B}, DESIGN.md §10) ==")
    print(f"  {len(events)} wire events over 3 interleaved waves "
          f"(80% participation, 15% in-flight sessions, version tags)")
    kw = dict(n_clients=K, n_params=P, payload=W, ring_capacity=64,
              buffer_size=B, staleness_mode="poly", staleness_alpha=1.0)
    prev = jnp.zeros((P,), jnp.float32)
    re_ = run_async_engine(EngineConfig(**kw), events, prev)
    rc = run_async_engine(EngineConfig(**kw, compile=True,
                                       shards=args.shards), events, prev)
    same = (np.array_equal(np.asarray(re_.globals_),
                           np.asarray(rc.globals_))
            and np.array_equal(np.asarray(re_.state.global_),
                               np.asarray(rc.state.global_))
            and np.array_equal(np.asarray(re_.state.total),
                               np.asarray(rc.state.total))
            and re_.updates == rc.updates and re_.stats == rc.stats)
    s = rc.stats
    shard_note = (f", {args.shards} worker shards" if args.shards > 1
                  else "")
    print(f"  {s.data_enqueued} pkts folded, {s.duplicates_dropped} dup "
          f"+ {s.phase_dropped} out-of-session dropped, "
          f"{s.updates_accepted} updates accepted, "
          f"{s.updates_in_flight} still in flight")
    print(f"  {s.emits} globals emitted (server version "
          f"{rc.state.version}), {rc.state.pending} updates carried in "
          f"the accumulator")
    hist = " ".join(f"s={k}:{v}" for k, v in
                    sorted(s.staleness_hist.items()))
    print(f"  staleness histogram (poly alpha=1 down-weighting): {hist}")
    print(f"  compiled scan fold{shard_note} bitwise == eager fold at "
          f"every emitted global: {same}")
    assert same, "async compiled fold diverged from the eager fold"


def hier_demo(args):
    """Hierarchical aggregation walkthrough (DESIGN.md §12).

    One lossy round is served three ways and the globals compared to
    the bit:

      flat   — the ordinary compiled engine (hosts=1), the reference;
      hier   — ``EngineConfig(hosts=H, shards=S)``: the drain schedule
               is partitioned by client ownership (host h owns the
               contiguous range [h*K//H, (h+1)*K//H)), each host's
               slice is demuxed through its *own* rings exactly as a
               real leaf host would see it, and the compiled scan folds
               all H*S partials with one psum per mesh level;
      twin   — ``run_hier_round``: H independent *eager* leaf engines
               plus an explicit host-level merge, the reference the
               compiled hier round must match even in approx mode.

    On integer payloads every partial sum is exactly representable, so
    regrouping the adds by host cannot change a single bit: flat ==
    hier == twin, at any (hosts, shards).
    """
    from repro.core.server import run_hier_round
    H, S = args.hosts, args.shards
    K, P, W = 12, 4096, 64
    rng = np.random.default_rng(0)
    # integer-valued params: f32 sums are order-independent, so the
    # three-way comparison below is exact to the bit (DESIGN.md §12)
    flats = jnp.asarray(rng.integers(-8, 9, (K, P)).astype(np.float32))
    prev = jnp.zeros((P,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(flats)
    events, _ = make_uplink_stream(rng, pk, loss_rate=0.0468,
                                   dup_rate=0.05)
    n_dev = len(jax.devices())
    layout = ("2-D ('host','worker') mesh" if n_dev >= H * S
              else f"single-device emulation ({n_dev} devices < "
                   f"{H}x{S})")
    print(f"\n== hierarchical round: hosts={H} x shards={S} "
          f"[{layout}] (DESIGN.md §12) ==")
    for h in range(H):
        lo, hi = (h * K) // H, ((h + 1) * K) // H
        print(f"  host {h} owns clients [{lo}, {hi})")
    for mode in ("exact", "approx"):
        kw = dict(n_clients=K, n_params=P, payload=W, ring_capacity=64,
                  mode=mode)
        flat = run_engine_round(EngineConfig(compile=True, **kw),
                                flats, prev, events)
        hier = run_engine_round(
            EngineConfig(compile=True, hosts=H, shards=S, **kw),
            flats, prev, events)
        twin = run_hier_round(EngineConfig(compile=True, hosts=H,
                                           shards=S, **kw),
                              flats, prev, events)
        vs_twin = (np.array_equal(np.asarray(hier.new_global),
                                  np.asarray(twin.new_global))
                   and np.array_equal(np.asarray(hier.counts),
                                      np.asarray(twin.counts)))
        vs_flat = np.array_equal(np.asarray(hier.new_global),
                                 np.asarray(flat.new_global))
        s = hier.stats
        print(f"  {mode:6s}: {s.data_enqueued} pkts over {H} hosts, "
              f"compiled hier == eager per-host twin: {vs_twin}; "
              f"== flat compiled round: {vs_flat}")
        # approx mode re-races per host: only the twin (which re-runs
        # the same per-host rings) is a bitwise reference there
        assert vs_twin, "hier round diverged from its eager twin"
        if mode == "exact":
            assert vs_flat, "exact hier round diverged from flat"


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true",
                    help="run each round as one compiled lax.scan "
                         "(EngineConfig(compile=True))")
    ap.add_argument("--shards", type=int, default=1,
                    help="worker-mesh shards for the compiled round "
                         "(implies --compile; DESIGN.md §7)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="hierarchical hosts for the compiled round: "
                         "per-host client ownership + two-level psum "
                         "(implies --compile; DESIGN.md §12)")
    ap.add_argument("--deadline", type=int, nargs="?", const=-1,
                    default=None, metavar="N",
                    help="deadline-closed partial-round demo: time out "
                         "a permanent straggler after N events (no N: "
                         "right after the healthy ENDs; DESIGN.md §8)")
    ap.add_argument("--churn", action="store_true",
                    help="multi-round churn-driver demo "
                         "(core/rounds.py: sampling + join/leave + "
                         "stragglers)")
    ap.add_argument("--int8", action="store_true",
                    help="compressed int8 uplink demo: quantized wire "
                         "payloads, dequantize fused into the round "
                         "(DESIGN.md §9)")
    ap.add_argument("--async", type=int, nargs="?", const=16,
                    default=None, dest="async_b", metavar="B",
                    help="async buffered-aggregation demo: emit a new "
                         "global every B folded updates, staleness-"
                         "weighted, no round barrier (DESIGN.md §10)")
    ap.add_argument("--attack", choices=["sign_flip", "scale", "nan"],
                    default=None, metavar="MODEL",
                    help="Byzantine demo: poison 2/10 clients with "
                         "MODEL and serve the round with and without "
                         "the robust finalize (DESIGN.md §11)")
    ap.add_argument("--agg", choices=["trimmed_mean", "median",
                                      "norm_clip"],
                    default="trimmed_mean", metavar="MODE",
                    help="robust agg_mode for the --attack demo "
                         "(default: trimmed_mean)")
    args = ap.parse_args()
    if args.shards > 1 or args.hosts > 1:
        args.compile = True
    if args.hosts > 1:
        hier_demo(args)
        return
    if args.attack is not None:
        attack_demo(args)
        return
    if args.async_b is not None:
        async_demo(args)
        return
    if args.deadline is not None:
        straggler_demo(args)
        if not (args.churn or args.int8):
            return
    if args.churn:
        churn_demo(args)
        return
    if args.int8:
        int8_demo(args)
        return
    K, P, W = 10, 4096, 64
    rng = np.random.default_rng(0)
    # integer-valued params make f32 sums order-independent, so the
    # engine/fused comparison below is exact to the bit
    client_flats = jnp.asarray(rng.integers(-8, 9, (K, P))
                               .astype(np.float32))
    prev_global = jnp.zeros((P,), jnp.float32)
    pk = jax.vmap(lambda f: packetize(f, W))(client_flats)

    events, up_mask = make_uplink_stream(rng, pk, loss_rate=0.0468,
                                         dup_rate=0.05)
    down_mask = jnp.asarray((rng.random((K, pk.shape[1])) > 0.0468)
                            .astype(np.float32))
    print(f"round: {K} clients x {pk.shape[1]} packets of {W} floats, "
          f"{len(events) - 2 * K} DATA packets on the wire "
          f"(4.68% loss, 5% duplication, shuffled)")

    for mode, cap in [("exact", 64), ("approx", 64)]:
        cfg = EngineConfig(n_clients=K, n_params=P, payload=W,
                           ring_capacity=cap, mode=mode,
                           compile=args.compile, shards=args.shards)
        res = run_engine_round(cfg, client_flats, prev_global, events,
                               down_mask=down_mask)
        s = res.stats
        engine = "compiled (one lax.scan)" if args.compile else "eager"
        if args.shards > 1:
            engine = (f"compiled, {args.shards} worker shards "
                      f"({min(args.shards, len(jax.devices()))} devices)")
        print(f"\n== {mode} server [{engine}] ==")
        print(f"  rx: {s.data_enqueued} unique packets ringed, "
              f"{s.duplicates_dropped} duplicates dropped at RX, "
              f"{s.control_replies} control replies")
        print(f"  workers: {s.batches_drained} ring batches "
              f"scatter-accumulated")
        print(f"  slots delivered: "
              f"{int(jnp.sum(res.counts > 0))}/{res.counts.shape[0]}")
        if mode == "exact":
            _, ng, cnt = fused_round_step(client_flats, up_mask, down_mask,
                                          prev_global, W, mode="exact")
            same = np.array_equal(np.asarray(res.new_global),
                                  np.asarray(ng))
            print(f"  bitwise identical to fused_round_step: {same}")
            assert same and np.array_equal(np.asarray(res.counts),
                                           np.asarray(cnt))
            exact_global = res.new_global
        else:
            err = float(jnp.linalg.norm(res.new_global - exact_global)
                        / jnp.linalg.norm(exact_global))
            print(f"  lock-free lost-update error vs exact: "
                  f"rel_l2={err:.3e} (ring capacity = race window)")


if __name__ == "__main__":
    main()
