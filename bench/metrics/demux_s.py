"""Host RX/demux seconds per round: the ``demux_events`` spans that the
benchmark's tap puts around each call the entry makes
(``core/engine_compiled.py::demux_events``), summed over the traced
window and divided by the rounds traced."""


def read(obs):
    spans = obs.trace.host_spans("demux_events")
    if not spans or not obs.n_rounds:
        return None
    return sum(d for _, d in spans) / 1e9 / obs.n_rounds
