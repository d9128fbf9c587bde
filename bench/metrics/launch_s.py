"""Host seconds per round handing the round to the device (the
accumulators' allocation and the jitted call): the program's
``fl.launch`` spans (``core/engine_compiled.py``), per traced round.
None where the program opens no such span."""
from span_time import per_round


def read(obs):
    return per_round(obs, "fl.launch")
