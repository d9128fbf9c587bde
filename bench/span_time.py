"""Seconds per traced round in named host events, for the readers of
the program's ``fl.*`` spans (``core/engine_compiled.py::SPANS``)."""
import trace_reduce


def per_round(obs, *names):
    """The union of the traced window's host events named ``names``, in
    seconds, divided by the rounds traced; None where no such event or
    round is there."""
    spans = [(s, s + d) for name in names
             for s, d in obs.trace.host_spans(name)]
    if not spans or not obs.n_rounds:
        return None
    return (sum(t - s for s, t in trace_reduce.union(spans)) / 1e9
            / obs.n_rounds)
