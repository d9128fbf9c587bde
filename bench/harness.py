"""One run of one benchmark cell: set-up, measured window, check.

A cell is ``<config>.<traffic>``: ``BENCHMARK.json`` names the
configuration (``configs/<config>.json``) and the traffic mix
(``traffic/<traffic>.json``, read by ``generators/<generator>.py``);
each per-layer metric is read by ``metrics/<name>.py`` and each
configuration's plain reference is ``references/<reference>.py``.  A
later cell or metric adds files and entries; nothing here names one.

The window drives the server's user entry, ``run_engine_round`` with
``EngineConfig(compile=True)``, one round after another as one FL job
does: round r+1 is handed over only once round r's new global is
ready, and its ``prev_global`` is round r's ``new_global``.  Set-up
builds a pool of rounds from the seed and serves each once, so every
schedule shape the window uses is compiled (or loaded from the
persistent cache) before the window opens.  The window cycles through
the pool and ends at a round boundary once ``seconds`` have passed.

After the window, every window round's global, per-slot counts,
acceptance mask and counters are compared with the plain reference
(``correct``).  With ``trace=True`` the first ``2 * pool`` rounds of
the window run under the profiler, and the per-layer metrics are read
from that trace and from what the rounds returned.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import itertools
import json
import math
import os
import sys
import tempfile
import time
import typing
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
for _p in (str(REPO / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce  # noqa: E402  (bench/, after the path set-up)

PEAKS_FILE = BENCH / "peaks.json"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    key = f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict          # the BENCHMARK.json entry
    config: dict            # configs/<config>.json
    mix: dict               # traffic/<traffic>.json
    per_layer: list         # BENCHMARK.json per_layer entries for it
    end_to_end: list


def load_cell(name: str, overrides: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``; ``overrides`` replaces
    keys of its configuration (the tests cut the sizes with it)."""
    spec = load_json(REPO / "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    wl = found[0]
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = load_json(REPO / conf["file"])
    config.update(overrides or {})
    mix = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name, wl, config, mix,
                [m for m in spec["per_layer"] if mine(m)],
                [m for m in spec["end_to_end"] if mine(m)])


def engine_config(config: dict):
    from repro.core.server import EngineConfig
    close = config["round_close"]
    if close not in ("all_end", "finalize"):
        raise ValueError(f"round_close must be all_end|finalize: {close}")
    return EngineConfig(
        n_clients=config["n_clients"], n_params=config["n_params"],
        payload=config["payload"], n_workers=config["n_workers"],
        ring_capacity=config["ring_capacity"],
        ring_assign=config["ring_assign"], mode=config["mode"],
        agg_mode=config["agg_mode"], compile=True,
        # closing at finalize: past every event (core/rounds.py's
        # CLOSE_AT_FINALIZE), so clients without an END time out there
        round_deadline=2 ** 62 if close == "finalize" else None)


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed % 2 ** 64)


def build_pool(seed: int, cell: Cell, n_slots: int):
    """(g0, rounds) from the seed: the initial global and
    ``pool_rounds`` rounds of traffic, each with fresh client updates."""
    gen = load_module("generators", cell.mix["generator"])
    conf, mix = cell.config, cell.mix
    rng = seed_rng(seed)
    g0 = (rng.standard_normal(conf["n_params"], dtype=np.float32)
          * np.float32(mix["payload_std"]))
    rounds = []
    for _ in range(mix["pool_rounds"]):
        pk, scales = gen.payload_tensor(rng, conf["n_clients"], n_slots,
                                        conf["payload"], conf["wire"],
                                        mix["payload_std"])
        rounds.append(gen.make_round(rng, pk, scales, mix))
    return g0, rounds


@contextlib.contextmanager
def compile_count():
    """Executables JAX compiled or loaded from its persistent cache while
    the block ran (each fires one backend-compile event)."""
    from jax import monitoring
    log = dict(compiles=0, cache_hits=0, compile_s=0.0)

    def on_duration(event, secs, **_):
        if event == BACKEND_COMPILE:
            log["compiles"] += 1
            log["compile_s"] += secs

    def on_event(event, **_):
        if event == CACHE_HIT:
            log["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def enable_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices


class SchedTap:
    """Wraps ``engine_compiled.demux_events``, the module attribute the
    entry calls, in a profiler span, and keeps what each call's drain
    schedule ships to the device."""

    def __init__(self):
        self.rounds = []
        self.recording = False

    @contextlib.contextmanager
    def installed(self):
        import jax
        from repro.core import engine_compiled as ec
        orig = ec.demux_events

        def demux_events(cfg, events, weights=None):
            with jax.profiler.TraceAnnotation("demux_events"):
                out = orig(cfg, events, weights)
            if self.recording:
                self.rounds.append(schedule_record(cfg, out[0]))
            return out
        ec.demux_events = demux_events
        try:
            yield self
        finally:
            ec.demux_events = orig


def schedule_record(cfg, sched) -> dict:
    """Counts of one round's drain schedule, from its arrays' shapes."""
    shipped = [sched.idx, sched.weights, sched.payloads, sched.scales]
    pk = sched.payloads
    return dict(accepted=int(sched.n_packets), payload=int(pk.shape[-1]),
                wire_itemsize=int(pk.dtype.itemsize),
                q8=sched.scales is not None, n_slots=int(cfg.n_slots),
                rows=int(pk.shape[0]),
                h2d_bytes=int(sum(a.nbytes for a in shipped
                                  if a is not None)),
                payload_bytes=int(pk.nbytes),
                useful_payload_bytes=int(sched.n_packets * pk.shape[-1]
                                         * pk.dtype.itemsize))


def serve(cfg, g, rnd):
    from repro.core.server import run_engine_round
    res = run_engine_round(cfg, None, g, rnd.events)
    res.new_global.block_until_ready()
    return res


class Served(typing.NamedTuple):
    """What the check reads of one window round, on the host."""
    new_global: np.ndarray
    counts: np.ndarray
    up_mask: np.ndarray
    stats: object


def start_fetch(res):
    """Start copying what the check reads of a round to the host; the
    copy runs while the next round's demux keeps the host busy."""
    for x in (res.new_global, res.counts, res.up_mask):
        x.copy_to_host_async()
    return res


def fetched(res) -> Served:
    """The host copy ``start_fetch`` began; the device buffers go with
    the round's result."""
    return Served(np.asarray(res.new_global), np.asarray(res.counts),
                  np.asarray(res.up_mask), res.stats)


@dataclasses.dataclass
class Window:
    sequence: list          # pool index of every window round
    results: list           # what the check reads of each (Served)
    times: list             # their seconds
    seconds: float
    compiles: dict
    traced: int = 0         # rounds under the profiler


def run_window(cfg, g, pool, seconds: float, *, trace_dir=None,
               tap: SchedTap | None = None) -> Window:
    import jax
    n_trace = 2 * len(pool) if trace_dir else 0
    seq, results, times = [], [], []
    with compile_count() as log:
        if n_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench_window")
            span.__enter__()
            tap.recording = True
        t0 = time.perf_counter()
        i = 0
        while True:
            p = i % len(pool)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_round"):
                res = start_fetch(serve(cfg, g, pool[p]))
            times.append(time.perf_counter() - t)
            if results:
                results[-1] = fetched(results[-1])
            seq.append(p)
            results.append(res)
            g = res.new_global
            i += 1
            if i == n_trace:
                span.__exit__(None, None, None)
                tap.recording = False
                jax.profiler.stop_trace()
            if time.perf_counter() - t0 >= seconds and i >= n_trace:
                break
        elapsed = time.perf_counter() - t0
    results[-1] = fetched(results[-1])
    return Window(seq, results, times, elapsed, log, n_trace)


def check(cell: Cell, cfg, g0, pool, warm_seq, win: Window) -> dict:
    """Compare every window round with the plain reference."""
    ref = load_module("references", cell.config["reference"])
    refs = [ref.round_reference(r.payloads, r.scales, r.accepted)
            for r in pool]
    limits = cell.config["limits"]
    close_finalize = cell.config["round_close"] == "finalize"
    worst = dict(accept_mismatch=0, count_mismatch=0, stats_mismatch=0,
                 fold_gap=0.0)
    failed = 0
    expected = itertools.islice(
        ref.chain(refs, g0, warm_seq + win.sequence, cfg.n_params),
        len(warm_seq), None)
    for p, res, (value, scale) in zip(win.sequence, win.results, expected):
        rnd = pool[p]
        up = np.asarray(res.up_mask) > 0
        counts = np.asarray(res.counts)
        st = res.stats
        want_stats = (rnd.accepted.sum(), rnd.duplicates,
                      rnd.stragglers if close_finalize else 0, 0, 0, 0)
        got_stats = (st.data_enqueued, st.duplicates_dropped,
                     st.stragglers_timed_out, st.phase_dropped,
                     st.late_dropped, st.malformed_dropped)
        row = dict(
            accept_mismatch=int(np.sum(up != rnd.accepted)),
            count_mismatch=int(np.sum(counts != refs[p].counts)),
            stats_mismatch=int(sum(a != b for a, b in
                                   zip(got_stats, want_stats))),
            fold_gap=ref.gap(np.asarray(res.new_global), value, scale))
        bad = any(row[k] > limits[k] for k in row)
        failed += bad
        for k in row:
            worst[k] = max(worst[k], row[k])
    return dict(worst=worst, failed=failed, rounds=len(win.results))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, t_start: float | None = None) -> dict:
    """One run; returns the result line's object, and prints the round
    times and where set-up went on standard error."""
    t_start = time.perf_counter() if t_start is None else t_start
    marks = {}

    def mark(name):
        marks[name] = time.perf_counter() - t_start
    enable_cache()
    import jax
    if require_chip:
        devices = check_devices(cell.workload["chips"])
    else:
        devices = jax.devices()
    mark("devices_s")
    cfg = engine_config(cell.config)
    g0, pool = build_pool(seed, cell, cfg.n_slots)
    mark("pool_s")
    tap = SchedTap()
    with contextlib.ExitStack() as stack:
        stack.enter_context(tap.installed())
        with compile_count() as warm_log:
            g = jax.numpy.asarray(g0)
            warm_seq = []
            for p, rnd in enumerate(pool):
                g = serve(cfg, g, rnd).new_global
                warm_seq.append(p)
        # the pool's ~10**5 event tuples stay alive all run: keep the
        # collector from walking them inside the window
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        tmp = stack.enter_context(tempfile.TemporaryDirectory(
            prefix="bench_trace_")) if trace else None
        win = run_window(cfg, g, pool, seconds, trace_dir=tmp, tap=tap)
        peak = devices[0].memory_stats() or {}
        observed = None
        if trace:
            t = time.perf_counter()
            observed = observe(cell, tmp, tap, win, devices)
            marks["trace_reduce_s"] = time.perf_counter() - t
    del g
    t = time.perf_counter()
    verdict = check(cell, cfg, g0, pool, warm_seq, win)
    marks["check_s"] = time.perf_counter() - t
    limits = cell.config["limits"]
    worst = verdict["worst"]
    correct = (verdict["rounds"] > 0 and verdict["failed"] == 0
               and all(worst[k] <= limits[k] for k in worst))
    device = dict(platform=devices[0].platform,
                  kind=devices[0].device_kind,
                  count=cell.workload["chips"],
                  memory_peak_bytes=int(peak.get("peak_bytes_in_use", 0)))
    progress = dict(round_times_s=win.times, window_s=win.seconds,
                    rounds=len(win.results), setup_s=setup_s,
                    warm_compiles=warm_log,
                    window_compiles=win.compiles["compiles"], **marks)
    print(json.dumps(progress), file=sys.stderr, flush=True)
    line = dict(correct=bool(correct), attempted=verdict["rounds"],
                failed=verdict["failed"])
    if trace:
        metrics, breakdown, busy = observed
        device.update(busy)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        metrics = dict(round_s=dict(value=win.seconds / len(win.results),
                                    unit="s"),
                       setup_s=dict(value=setup_s, unit="s"))
        line.update(metrics={m["name"]: metrics[m["name"]]
                             for m in cell.end_to_end},
                    device=device)
    line["check"] = {k: dict(value=json_number(worst[k]), limit=limits[k])
                     for k in worst}
    return line


def json_number(x):
    """A number JSON can hold: a gap that is not finite reads 1e308."""
    return x if math.isfinite(x) else 1e308


def observe(cell: Cell, trace_dir: str, tap: SchedTap, win: Window,
            devices):
    """Per-layer metrics, busy/window seconds and the breakdown from the
    traced rounds."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    tr = trace_reduce.load(path[0], window_span="bench_window")
    obs = Observation(config=cell.config,
                      peaks=peaks_for(devices[0].device_kind),
                      rounds=tap.rounds[:win.traced], trace=tr,
                      window_compiles=win.compiles["compiles"])
    metrics = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    busy = dict(busy_s=tr.busy_ns() / 1e9, window_s=tr.window_ns() / 1e9)
    return metrics, tr.breakdown(), busy


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    peaks = load_json(PEAKS_FILE)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"{PEAKS_FILE.name}")
    return peaks[kind]


@dataclasses.dataclass
class Observation:
    """What a per-layer metric's reader may read."""
    config: dict
    peaks: dict
    rounds: list            # schedule_record of each traced round
    trace: "trace_reduce.Trace"
    window_compiles: int

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        line = run(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, v in line["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
