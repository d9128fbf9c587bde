"""Host seconds per round building the drain schedule
(``build_drain_schedule``): the program's ``fl.demux.schedule`` span
(``core/engine_compiled.py::demux_events``), per traced round.  None
where the program opens no such span."""
from span_time import per_round


def read(obs):
    return per_round(obs, "fl.demux.schedule")
