"""Host-to-device seconds per round: the time from the round's arrays
being handed over to the device holding them.  The union of

- the program's ``fl.h2d`` spans (``core/engine_compiled.py``), where
  the Python thread hands the arrays to ``jnp.asarray``;
- the runtime's ``XlaLinearize`` events, where PjRt's own threads lay
  each array out for the device (``Transpose::Execute`` inside), mostly
  after ``fl.h2d`` and ``fl.round`` have closed;
- the copy's flight: in a device-idle gap, from the end of the last
  layout in it to the device's next op, where no ``fl.*`` span is open.

Divided by the rounds traced.  None where the program opens no
``fl.h2d`` span."""
import trace_reduce

SPAN, LAYOUT = "fl.h2d", "XlaLinearize"


def idle_gaps(trace):
    """The device-idle intervals of the traced window."""
    edges = [trace.window[0]]
    for s, t in trace.busy_intervals():
        edges += [s, t]
    edges.append(trace.window[1])
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def read(obs):
    tr = obs.trace
    if not tr.host_spans(SPAN) or not obs.n_rounds:
        return None
    held = [(s, s + d) for name in (SPAN, LAYOUT)
            for s, d in tr.host_spans(name)]
    program = [(e.start_ns, e.end_ns) for e in tr.host
               if e.name.startswith("fl.")]
    for a, b in idle_gaps(tr):
        ends = [t for _, t in held if a < t < b]
        if not ends:
            continue
        last = max(ends)
        if not any(s < b and t > last for s, t in program):
            held.append((last, b))
    return (sum(t - s for s, t in trace_reduce.union(held)) / 1e9
            / obs.n_rounds)
