"""Device seconds per round in the fold kernel: the Pallas
``packet_scatter_accum`` kernels (f32 and q8) of
``kernels/packet_scatter.py``, as they run inside the round's scan.

The profiler names a device op after its HLO instruction, and a Pallas
kernel lowers to a ``tpu_custom_call`` whose instruction does not carry
the kernel's name.  So an op counts as the fold when it is a
``tpu_custom_call`` inside the round's program, ``MODULE``
(``engine_compiled._round_device``): in the mean round that program
runs no other kernel.  Reads None when no op matches.
"""

MODULE = "jit__round_device"
TARGET = 'custom_call_target="tpu_custom_call"'


def is_fold(event) -> bool:
    return (event.meta.get("module") == MODULE
            and TARGET in event.meta.get("hlo", ""))


def read(obs):
    if not obs.n_rounds:
        return None
    total = sum(e.dur_ns for e in obs.trace.device_ops if is_fold(e))
    if total == 0:
        return None
    return total / 1e9 / obs.n_rounds
