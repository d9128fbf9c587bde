"""Host seconds per round stacking uplink payloads into arrays (the
f32 candidate rows, and the accepted rows): the program's
``fl.demux.stack`` spans (``core/engine_compiled.py::demux_events``),
per traced round.  None where the program opens no such span."""
from span_time import per_round


def read(obs):
    return per_round(obs, "fl.demux.stack")
