"""Reduction of a JAX profiler trace (``.xplane.pb``) to the events the
per-layer metrics read.

- Device ops: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane.  The profiler names each after its HLO
  instruction's text (``%closed_call.8 = (...) custom-call(...),
  custom_call_target="tpu_custom_call", ...``): an op's ``name`` here is
  the instruction's name (``closed_call.8``), ``meta["hlo"]`` the whole
  text, and ``meta["module"]`` the jitted program, from the ``XLA
  Modules`` line, whose span holds the op (``jit__round_device``).
- Host spans: every event of the ``/host:CPU`` plane, by name; the
  benchmark's own spans (``bench_window``, ``bench_round``,
  ``demux_events``) are among them.
- The window: the one ``window_span`` host span.  Device ops and host
  spans are clipped to it.

The profiler puts host and device events on one clock, so a device
op's interval can be set against the host span it falls in.
"""
from __future__ import annotations

import bisect
import dataclasses

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
# ops that only hold other ops: counted as busy, left out of the top ops
CONTAINERS = ("while", "conditional", "call")
TOP = 10


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    meta: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t - s, e.meta))
    return out


def union(intervals):
    """Merged (start, end) intervals, sorted."""
    merged = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


@dataclasses.dataclass
class Trace:
    window: tuple           # (start_ns, end_ns)
    device_ops: list        # [Event] clipped to the window, all chips
    host: list              # [Event] clipped to the window
    n_devices: int

    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def host_spans(self, name: str):
        return [(e.start_ns, e.dur_ns) for e in self.host if e.name == name]

    def busy_intervals(self):
        return union((e.start_ns, e.end_ns) for e in self.device_ops)

    def busy_ns(self) -> float:
        """Union of device-op intervals, averaged over the chips."""
        by_dev = {}
        for e in self.device_ops:
            by_dev.setdefault(e.meta.get("device", "0"), []).append(
                (e.start_ns, e.end_ns))
        if not by_dev:
            return 0.0
        total = sum(sum(t - s for s, t in union(iv))
                    for iv in by_dev.values())
        return total / max(self.n_devices, 1)

    def breakdown(self) -> dict:
        """The device ops that took most time, and the longest idle gaps
        labelled by the shortest host span that covers most of each."""
        per_op = {}
        for e in self.device_ops:
            if e.name.split(".")[0] in CONTAINERS:
                continue
            key = f"{e.meta.get('module', '?')}/{e.name}"
            if "tpu_custom_call" in e.meta.get("hlo", ""):
                key += " (tpu_custom_call)"
            per_op[key] = per_op.get(key, 0.0) + e.dur_ns
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = []
        edges = [self.window[0]]
        for s, t in self.busy_intervals():
            edges += [s, t]
        edges.append(self.window[1])
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
        labelled = {}
        for a, b in gaps:
            name = self._host_label(a, b)
            labelled[name] = labelled.get(name, 0.0) + (b - a)
        return dict(
            device_ops=[[k, v / 1e9] for k, v in ops],
            idle_gaps=[[k, v / 1e9] for k, v in
                       sorted(labelled.items(), key=lambda kv: -kv[1])])

    def _host_label(self, a, b) -> str:
        best = None
        for e in self.host:
            over = min(e.end_ns, b) - max(e.start_ns, a)
            if over >= 0.5 * (b - a) and (best is None
                                          or e.dur_ns < best.dur_ns):
                best = e
        return best.name if best else "no host span"


def _op_name(text: str) -> str:
    """``%closed_call.8 = (...) custom-call(...)`` -> ``closed_call.8``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _module_of(spans, t):
    """Name of the module span (sorted by start) that holds time t."""
    i = bisect.bisect_right(spans, (t, chr(0x10FFFF))) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][2]:
        return spans[i][1]
    return "?"


def load(path: str, window_span: str = "bench_window") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, ops, n_dev = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = plane.name[len(DEVICE_PLANE):]
            if not dev.isdigit():
                continue
            n_dev += 1
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.name.split("(", 1)[0], e.end_ns)
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else []))
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else []):
                ops.append(Event(_op_name(e.name), e.start_ns,
                                 e.duration_ns,
                                 dict(hlo=e.name, device=dev,
                                      module=_module_of(modules,
                                                        e.start_ns))))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.append(Event(e.name, e.start_ns, e.duration_ns,
                                      {}))
    spans = [e for e in host if e.name == window_span]
    if len(spans) != 1:
        raise ValueError(f"expected one {window_span!r} span, found "
                         f"{len(spans)}")
    lo, hi = spans[0].start_ns, spans[0].end_ns
    return Trace((lo, hi), _clip(ops, lo, hi), _clip(host, lo, hi), n_dev)
