"""The served round's profiler spans and the fold kernels' names.

``core/engine_compiled.py`` opens the spans in ``SPANS`` around the
phases of ``run_compiled_round`` (``jax.profiler.TraceAnnotation``).
Here a small f32 round and a small q8 round closed at finalize with
stragglers are served through ``run_engine_round(EngineConfig(
compile=True))`` under the profiler, and the trace's host spans are
read back: each phase opens a fixed number of times a round, nests in
its parent, and opens as often in a round ten times the size (a span
per event would not).  The fold kernels and the robust finalize are
exported for TPU and their custom call must carry the kernel's name.
"""
import collections
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine_compiled as ec
from repro.core.aggregation import quantize_packets
from repro.core.packets import packetize
from repro.core.protocol import Kind
from repro.core.rounds import CLOSE_AT_FINALIZE
from repro.core.server import (EngineConfig, make_uplink_stream,
                               run_engine_round)
from repro.kernels.packet_scatter import (packet_scatter_accum_pallas,
                                          packet_scatter_accum_q8_pallas,
                                          robust_finalize_pallas)

W_F32, W_Q8 = 367, 1464
STRAGGLERS = 2

# openings a round of each span, by path: the f32 round stacks its
# candidate rows for the malformed check and then the accepted ones;
# the q8 round stacks only the accepted int8 rows
OPENINGS = {
    "f32": {"fl.round": 1, "fl.demux": 1, "fl.demux.scan": 1,
            "fl.demux.gate": 2, "fl.demux.stack": 2,
            "fl.demux.schedule": 1, "fl.h2d": 2, "fl.launch": 2},
    "q8_finalize": {"fl.round": 1, "fl.demux": 1, "fl.demux.scan": 1,
                    "fl.demux.gate": 2, "fl.demux.stack": 1,
                    "fl.demux.schedule": 1, "fl.h2d": 2, "fl.launch": 2},
}
DEMUX_PHASES = ("fl.demux.scan", "fl.demux.gate", "fl.demux.stack",
                "fl.demux.schedule")
ROUND_PHASES = ("fl.demux", "fl.h2d", "fl.launch")


def _round(path, n_clients, n_slots, seed):
    """(cfg, events) of one lossy, duplicated, reordered round."""
    rng = np.random.default_rng(seed)
    w = W_F32 if path == "f32" else W_Q8
    flats = jnp.asarray(rng.normal(size=(n_clients, n_slots * w - 3))
                        .astype(np.float32))
    pk = jax.vmap(lambda f: packetize(f, w))(flats)
    cfg = EngineConfig(n_clients=n_clients, n_params=flats.shape[1],
                       payload=w, n_workers=2, ring_capacity=16,
                       compile=True)
    if path == "f32":
        events, _ = make_uplink_stream(rng, pk, loss_rate=0.1, dup_rate=0.1)
        return cfg, events
    q, sc = quantize_packets(pk)
    events, _ = make_uplink_stream(rng, q, loss_rate=0.1, dup_rate=0.1,
                                   scales=sc)
    # the last clients stall: no END, and half their DATA never arrives
    late = set(range(n_clients - STRAGGLERS, n_clients))
    events = [(p, x) for p, x in events
              if p.client not in late
              or p.kind is Kind.START
              or (p.kind is Kind.DATA and p.index < n_slots // 2)]
    return dataclasses.replace(cfg, round_deadline=CLOSE_AT_FINALIZE), events


def _serve_traced(tmp_path, rounds):
    """Serve each (cfg, events) under the profiler -> (per round, the
    ``fl.*`` host spans as {name: [(start_ns, end_ns)]}; the results)."""
    results = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for cfg, events in rounds:
            g = jnp.zeros(cfg.n_params, jnp.float32)
            res = run_engine_round(cfg, None, g, events)
            res.new_global.block_until_ready()
            results.append(res)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("fl.")]
    outer = sorted((s, t) for name, s, t in spans if name == "fl.round")
    assert len(outer) == len(rounds)
    per_round = []
    for s, t in outer:
        mine = collections.defaultdict(list)
        for name, a, b in spans:
            if s <= a and b <= t:
                mine[name].append((a, b))
        per_round.append(dict(mine))
    assert sum(len(v) for r in per_round for v in r.values()) == len(spans)
    return per_round, results


@pytest.fixture(scope="module", params=sorted(OPENINGS))
def traced(request, tmp_path_factory):
    """A small round and one ten times its events, each served traced
    twice (the first pair compiles)."""
    path = request.param
    small = _round(path, n_clients=2 + STRAGGLERS, n_slots=3, seed=1)
    large = _round(path, n_clients=8 + STRAGGLERS, n_slots=32, seed=2)
    assert len(large[1]) >= 10 * len(small[1])
    return path, _serve_traced(tmp_path_factory.mktemp(path),
                               [small, large, small, large])


def test_spans_constant_names_every_phase():
    assert ec.SPANS == ("fl.round", "fl.demux") + DEMUX_PHASES + (
        "fl.h2d", "fl.launch")
    for want in OPENINGS.values():
        assert set(want) == set(ec.SPANS)


def test_each_span_opens_per_phase(traced):
    path, (per_round, results) = traced
    for spans in per_round:
        assert {k: len(v) for k, v in spans.items()} == OPENINGS[path]
    if path == "q8_finalize":
        assert all(r.stats.stragglers_timed_out == STRAGGLERS
                   for r in results)


def test_phase_spans_nest_in_their_parent(traced):
    _, (per_round, _) = traced
    for spans in per_round:
        (d0, d1), = spans["fl.demux"]
        for name in DEMUX_PHASES:
            assert all(d0 <= a and b <= d1 for a, b in spans[name]), name
        (r0, r1), = spans["fl.round"]
        for name in ROUND_PHASES:
            assert all(r0 <= a and b <= r1 for a, b in spans[name]), name
        # the demux phases do not overlap: they tile fl.demux
        phases = sorted(iv for n in DEMUX_PHASES for iv in spans[n])
        assert all(b <= c for (_, b), (c, _) in zip(phases, phases[1:]))


def test_openings_do_not_grow_with_events(traced):
    _, (per_round, _) = traced
    small, large = per_round[2], per_round[3]   # both already compiled
    assert ({k: len(v) for k, v in small.items()}
            == {k: len(v) for k, v in large.items()})


@pytest.mark.parametrize("name, kernel, w, q8", [
    ("fold_f32", packet_scatter_accum_pallas, W_F32, False),
    ("fold_q8", packet_scatter_accum_q8_pallas, W_Q8, True),
])
def test_fold_kernel_carries_its_name_for_tpu(name, kernel, w, q8):
    S, N = 16, 128
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct((N, w), jnp.int8 if q8 else f32)]
    if q8:
        shapes.append(jax.ShapeDtypeStruct((N,), f32))
    shapes += [jax.ShapeDtypeStruct((N,), jnp.int32),
               jax.ShapeDtypeStruct((N,), f32),
               jax.ShapeDtypeStruct((S, w), f32),
               jax.ShapeDtypeStruct((S, 1), f32)]
    exported = jax.export.export(jax.jit(kernel), platforms=["tpu"])(*shapes)
    text = exported.mlir_module()
    assert "tpu_custom_call" in text
    assert f'kernel_metadata = "{{\\0A\\22kernel\\22:\\22{name}\\22' in text


def test_robust_finalize_carries_its_name_for_tpu():
    S, K = 16, 10
    f32 = jnp.float32
    exported = jax.export.export(jax.jit(robust_finalize_pallas),
                                 platforms=["tpu"])(
        jax.ShapeDtypeStruct((S, K, W_F32), f32),
        jax.ShapeDtypeStruct((S, K), f32))
    text = exported.mlir_module()
    assert "tpu_custom_call" in text
    assert ('kernel_metadata = "{\\0A\\22kernel\\22:'
            '\\22robust_finalize\\22' in text)
