"""Device seconds per traced round in named Pallas kernels, for the
readers of the kernels' ``metadata`` names (``kernels/packet_scatter.py``).

A Pallas call lowers to a ``tpu_custom_call`` whose instruction text
carries the call's ``metadata`` as ``kernel_metadata={"kernel":"<name>"}``
(``trace_reduce`` keeps that text as an op's ``meta["hlo"]``), so a
kernel is found by its name inside a jitted program, whatever the
instruction is called."""
import re

TARGET = 'custom_call_target="tpu_custom_call"'
_NAME = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')


def kernel_name(event):
    """The ``metadata`` name of a device op that is a Pallas kernel, or
    None."""
    hlo = event.meta.get("hlo", "")
    if TARGET not in hlo:
        return None
    found = _NAME.search(hlo)
    return found.group(1) if found else None


def per_round(obs, module: str, names):
    """Seconds per traced round of the ops in ``module`` whose kernel
    name is one of ``names``; None where no such op or round is there."""
    if not obs.n_rounds:
        return None
    total = sum(e.dur_ns for e in obs.trace.device_ops
                if e.meta.get("module") == module
                and kernel_name(e) in names)
    if total == 0:
        return None
    return total / 1e9 / obs.n_rounds
