"""Host seconds per round in RX demux's per-event pass over the
round's events: the program's ``fl.demux.scan`` span
(``core/engine_compiled.py::demux_events``), per traced round.  None
where the program opens no such span."""
from span_time import per_round


def read(obs):
    return per_round(obs, "fl.demux.scan")
