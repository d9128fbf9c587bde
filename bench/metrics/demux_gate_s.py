"""Host seconds per round in RX demux's numpy gate (client frames,
deadline cut, malformed check, dedup, uplink mask): the program's
``fl.demux.gate`` spans (``core/engine_compiled.py::demux_events``),
per traced round.  None where the program opens no such span."""
from span_time import per_round


def read(obs):
    return per_round(obs, "fl.demux.gate")
