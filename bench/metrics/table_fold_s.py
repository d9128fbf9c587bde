"""Device seconds per round in the robust round's table fold: the
Pallas fold kernels (``fold_f32``, ``fold_q8``) as they run inside
``engine_compiled._robust_round_device``, folding each drained batch
into the ``(S*K, W)`` per-slot client table.  Reads None when no op
matches (the mean round's program is another module)."""
from kernel_time import per_round

MODULE = "jit__robust_round_device"
KERNELS = ("fold_f32", "fold_q8")


def read(obs):
    return per_round(obs, MODULE, KERNELS)
