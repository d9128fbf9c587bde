"""Device seconds per round in the robust finalize kernel
(``robust_finalize_pallas``, named ``robust_finalize``): the per-slot
rank-select and trimmed-band mean over the client table, inside
``engine_compiled._robust_round_device``.  Reads None when no op
carries the name."""
from kernel_time import per_round

MODULE = "jit__robust_round_device"
KERNELS = ("robust_finalize",)


def read(obs):
    return per_round(obs, MODULE, KERNELS)
