"""Sharding rules: logical-axis activation constraints + path-based param specs.

The mesh is ``('data','model')`` single-pod or ``('pod','data','model')``
multi-pod.  Parallelism mapping:

- DP   : batch over ``('pod','data')``
- TP   : heads / d_ff / vocab over ``'model'`` (GSPMD pads uneven head counts)
- EP   : MoE expert dim over ``'model'`` (see models/moe.py shard_map)
- FSDP : second param dim over ``'data'`` (ZeRO-3 style; XLA inserts the
         per-layer all-gather, whose transpose is the reduce-scatter of grads)
- SP   : optional sequence sharding over ``'model'`` for long prefill
- KV   : decode KV cache sequence-sharded over ``'model'`` (flash-decode)

Everything is a no-op when ``ctx is None`` (single-device smoke tests).

The packet-path round engine uses two *separate* meshes defined at the
bottom of this module (they shard the drain schedule, never params or
batch — every model-parallel knob above is off in their ``ParallelCtx``):

- ``worker_mesh(N)``: the 1-D ``('worker',)`` mesh of
  ``EngineConfig(shards=N)`` (DESIGN.md §7);
- ``host_worker_mesh(H, S)``: the 2-D ``('host','worker')`` mesh of
  ``EngineConfig(hosts=H, shards=S)`` (DESIGN.md §12), with client
  ownership ranges from ``client_range``/``client_owner``/``HostCtx``.

Invariants the tests pin (tests/test_engine_sharded.py,
tests/test_engine_hier.py):

- on the CPU both factories return ``None`` below the device count,
  and the engines then run a vmap emulation of the identical dataflow —
  *bitwise* the same as the mesh path; on an accelerator they raise
  instead (``_too_few_devices``), so a sharded round never runs on one
  chip without saying so;
- the ownership ranges ``[h*K//H, (h+1)*K//H)`` tile the client set
  exactly (a partition: every client owned once) and are balanced to
  within one client;
- ``HostCtx.from_process`` is the only place ``jax.process_index`` is
  consulted, so single-process tests exercise every host's range.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Parallel context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Mesh
    fsdp: bool = True                 # shard params over 'data' (ZeRO-3)
    seq_shard: bool = False           # sequence parallelism for prefill
    shard_batch: bool = True          # False for global_batch < n_dp (long_500k)
    kv_shard: str = "seq"             # decode KV: 'seq'|'seq2'|'heads'|'none'
    kv_quant: bool = False            # int8 KV cache (decode; ~2x HBM saving)
    # decode-time tied-embedding layout: store the table vocab-sharded so
    # the LM-head use needs no per-step (V,D) reshard; the embed lookup
    # pays a tiny psum over 'model' instead (fine at decode batch sizes)
    vocab_sharded_embed: bool = False
    attn_q_chunk: int = 512           # flash-attention q block
    attn_kv_chunk: int = 1024         # flash-attention kv block
    attn_causal_skip: bool = False    # unrolled diagonal (skips masked kv
                                      # blocks; ~2x fewer attention flops)
    scan_remat: bool = True           # remat each block inside the layer scan
    moe_capacity_factor: float = 1.25
    # decode-time MoE: keep expert weights stationary (E over 'model',
    # hidden over 'data') and all-gather the *tokens* instead of the
    # weights — decode batches are tiny, so this removes the per-layer
    # FSDP weight gather entirely (§Perf lever).
    moe_decode_tp: bool = False
    # gradient-accumulation microbatching: split the global batch into N
    # sequential microbatches inside train_step — divides activation
    # memory by N with identical per-step math/collective totals (the
    # 16 GB/chip feasibility lever for the train cells; §Perf).
    microbatches: int = 1
    ssm_scan_chunk: int = 128         # chunked-remat scan length for SSM/RWKV
    # FL aggregation mode for fl_round (paper technique): exact | approx | int8
    agg_mode: str = "exact"

    # -- axis helpers --------------------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def worker_axis(self) -> Optional[str]:
        return WORKER_AXIS if WORKER_AXIS in self.axis_names else None

    @property
    def host_axis(self) -> Optional[str]:
        return HOST_AXIS if HOST_AXIS in self.axis_names else None

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axis_names if a in ("pod", "data"))

    @property
    def has_pod(self) -> bool:
        return "pod" in self.axis_names

    @property
    def fsdp_axis(self) -> Optional[str]:
        return "data" if (self.fsdp and "data" in self.axis_names) else None

    def axis_size(self, name: str) -> int:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[name]


# ---------------------------------------------------------------------------
# Worker axis: the packet-engine shard dimension (DESIGN.md §7)
# ---------------------------------------------------------------------------
# The paper's server splits one round's aggregation across the DPU's
# worker cores, each folding its ring drains into a per-core partial sum
# combined at END.  The sharded round engine maps those cores onto a 1-D
# ``('worker',)`` device mesh: core/engine_compiled.py demuxes the drain
# schedule per shard and psums the shard-local (total, counts) partials.
# DESIGN.md §12 grows that mesh a second, outer level: a ``'host'``
# axis whose rows are leaf aggregation hosts, each owning a contiguous
# client range — the paper's DPU-vs-host split generalized to a
# two-level tree (NIC cores within a host, hosts across machines).

WORKER_AXIS = "worker"
HOST_AXIS = "host"


@functools.lru_cache(maxsize=None)
def worker_mesh(n_shards: int) -> Optional[Mesh]:
    """1-D ``('worker',)`` mesh over the first ``n_shards`` devices.

    Returns None when a CPU platform exposes fewer devices than shards:
    callers fall back to a single-device emulation of the same
    partial-sum dataflow, which is bitwise identical — CI's multi-device
    lane runs the real mesh under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  On an
    accelerator too few devices is an error, not an emulation.
    """
    if n_shards <= 1:
        return None
    devices = jax.devices()
    if len(devices) < n_shards:
        return _too_few_devices(devices, n_shards, f"shards={n_shards}")
    return Mesh(np.asarray(devices[:n_shards]), (WORKER_AXIS,))


def _too_few_devices(devices, needed: int, ask: str) -> None:
    """The CPU emulates a mesh it lacks (returns None); an accelerator
    raises, because an emulated fold would hide that the round ran on
    fewer chips than the configuration asks for."""
    platform = devices[0].platform
    if platform == "cpu":
        return None
    raise ValueError(
        f"EngineConfig({ask}) needs {needed} {platform} devices, this "
        f"process sees {len(devices)}; the single-device emulation of a "
        f"sharded round runs only on the CPU")


def worker_ctx(n_shards: int) -> Optional[ParallelCtx]:
    """ParallelCtx over the worker mesh (None when no mesh is possible).

    The packet engine shards no parameters and no batch — only the drain
    schedule — so the model-parallel knobs are all off.
    """
    mesh = worker_mesh(n_shards)
    if mesh is None:
        return None
    return ParallelCtx(mesh=mesh, fsdp=False, shard_batch=False)


# ---------------------------------------------------------------------------
# Host axis: hierarchical multi-host aggregation (DESIGN.md §12)
# ---------------------------------------------------------------------------

def client_range(host: int, n_hosts: int, n_clients: int
                 ) -> Tuple[int, int]:
    """Half-open client range ``[lo, hi)`` owned by ``host``.

    The balanced contiguous-block partition: host ``h`` owns clients
    ``[h·K//H, (h+1)·K//H)``.  The blocks tile ``[0, K)`` exactly —
    every client is owned by exactly one host and the union over hosts
    is the full client set (the schedule-partition property,
    tests/test_engine_hier.py) — and sizes differ by at most one, so no
    leaf host carries more than its share of the demux load.
    """
    if not 0 <= host < n_hosts:
        raise ValueError(f"host must be in [0, {n_hosts}), got {host}")
    return (host * n_clients) // n_hosts, ((host + 1) * n_clients) // n_hosts


def client_owner(clients, n_hosts: int, n_clients: int) -> np.ndarray:
    """Vectorized ownership lookup: client ids -> owning host ids.

    Inverts :func:`client_range` with one ``searchsorted`` against the
    H range boundaries, so the per-host demux
    (``engine_compiled.partition_schedule_by_host``) costs one pass
    over the accepted arrivals, not a per-packet Python dispatch.
    """
    bounds = np.asarray([((h + 1) * n_clients) // n_hosts
                         for h in range(n_hosts)], np.int64)
    return np.searchsorted(bounds, np.asarray(clients, np.int64),
                           side="right")


@dataclasses.dataclass(frozen=True)
class HostCtx:
    """One leaf host's identity in the aggregation tree (DESIGN.md §12).

    ``host`` is this process's row on the ``'host'`` mesh axis; in a
    real multi-process deployment it is ``jax.process_index()``
    (:meth:`from_process`), while the emulated single-machine setup —
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` partitioning
    one CPU into N devices — enumerates HostCtx values explicitly (the
    eager per-host twin ``server.run_hier_round`` does exactly that).
    The context answers the only question the demux needs: which client
    sessions does this host own?
    """
    host: int
    n_hosts: int
    n_clients: int

    def __post_init__(self):
        if not 0 <= self.host < self.n_hosts:
            raise ValueError(
                f"host must be in [0, {self.n_hosts}), got {self.host}")

    @property
    def clients(self) -> Tuple[int, int]:
        """Owned half-open client range ``[lo, hi)``."""
        return client_range(self.host, self.n_hosts, self.n_clients)

    def owns(self, client: int) -> bool:
        lo, hi = self.clients
        return lo <= client < hi

    @classmethod
    def from_process(cls, n_clients: int) -> "HostCtx":
        """The real multi-process identity: one leaf host per JAX
        process (``jax.process_index`` / ``jax.process_count``)."""
        return cls(jax.process_index(), jax.process_count(), n_clients)


@functools.lru_cache(maxsize=None)
def host_worker_mesh(n_hosts: int, n_shards: int) -> Optional[Mesh]:
    """2-D ``('host', 'worker')`` mesh over the first
    ``n_hosts · n_shards`` devices (DESIGN.md §12).

    Row ``h`` holds host ``h``'s worker shards, so the two-level
    combine is one ``psum`` per mesh level: worker-level within a row,
    host-level across rows.  Returns None when a CPU platform exposes
    too few devices — callers fall back to the nested-vmap emulation of
    the same dataflow, which is bitwise identical on exactly-representable
    sums; the CI multi-device lane runs the real mesh (8 emulated
    devices cover up to ``(hosts=4, shards=2)``).  On an accelerator too
    few devices raises.
    """
    n = n_hosts * n_shards
    if n <= 1:
        return None
    devices = jax.devices()
    if len(devices) < n:
        return _too_few_devices(devices, n,
                                f"hosts={n_hosts}, shards={n_shards}")
    return Mesh(np.asarray(devices[:n]).reshape(n_hosts, n_shards),
                (HOST_AXIS, WORKER_AXIS))


def host_ctx(n_hosts: int, n_shards: int) -> Optional[ParallelCtx]:
    """ParallelCtx over the 2-D (host, worker) mesh (None when the
    platform cannot host it).  Like :func:`worker_ctx`, only the drain
    schedule is partitioned — every model-parallel knob stays off.
    """
    mesh = host_worker_mesh(n_hosts, n_shards)
    if mesh is None:
        return None
    return ParallelCtx(mesh=mesh, fsdp=False, shard_batch=False)


# ---------------------------------------------------------------------------
# Activation constraints by logical axes
# ---------------------------------------------------------------------------

def _resolve(ctx: ParallelCtx, logical: Optional[str], kind: str):
    if logical is None:
        return None
    if logical == "batch":
        if not ctx.shard_batch:
            return None
        dp = ctx.dp_axes
        return dp if len(dp) > 1 else (dp[0] if dp else None)
    if logical == "seq":
        return "model" if ctx.seq_shard else None
    if logical == "kv_seq":
        if ctx.kv_shard == "seq":
            return "model"
        if ctx.kv_shard == "seq2":         # long-context: 2-axis seq shard
            dp = ctx.dp_axes
            return tuple(dp) + ("model",)
        return None
    if logical == "heads":
        return "model"
    if logical == "kv_heads":
        return "model" if ctx.kv_shard == "heads" else None
    if logical in ("mlp", "vocab", "expert", "dinner"):
        return "model"
    if logical == "embed":
        return None
    raise ValueError(f"unknown logical axis {logical!r} ({kind})")


def shard_act(x, logical_axes, ctx: Optional[ParallelCtx]):
    """with_sharding_constraint by logical axis names; no-op without ctx."""
    if ctx is None:
        return x
    spec = P(*[_resolve(ctx, a, "act") for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs (path-regex rules)
# ---------------------------------------------------------------------------
# Matched against the '/'-joined pytree path of each leaf.  F = fsdp axis
# (or None).  Order matters: first match wins.

def _param_rules(f):
    return [
        # embeddings / head.  The token-embedding gather must stay local, so
        # the table is sharded on d_model (not vocab); the LM head is sharded
        # on vocab so logits + CE stay sharded.  Tied embeddings re-constrain
        # the table to P('model', None) at the head matmul.
        (r"embed/table$",        P(None, "model")),         # (V, D)
        (r"lm_head/w$",          P(f, "model")),            # (D, V)
        # attention
        (r"attn/wq$",            P(f, "model", None)),      # (D, H, hd)
        (r"attn/w[kv]$",         P(f, "model", None)),      # (D, KV, hd)
        (r"attn/wo$",            P("model", None, f)),      # (H, hd, D)
        (r"attn/b[qkv]$",        P("model", None)),         # (H|KV, hd)
        (r"attn/bo$",            P(None)),
        # dense mlp
        (r"mlp/w1$",             P(f, "model")),
        (r"mlp/w3$",             P(f, "model")),
        (r"mlp/w2$",             P("model", f)),
        (r"mlp/b1$",             P("model",)),
        (r"mlp/b3$",             P("model",)),
        (r"mlp/b2$",             P(None)),
        # MoE: experts sharded over 'model' (EP); hidden dim over 'data'
        # (ZeRO-3 in training; weight-stationary 2D TP in decode)
        (r"moe/router$",         P(None, None)),
        (r"moe/w1$",             P("model", None, "data")),  # (E, D, Fe)
        (r"moe/w3$",             P("model", None, "data")),
        (r"moe/w2$",             P("model", "data", None)),  # (E, Fe, D)
        (r"moe/(shared|residual)/w1$", P(f, "model")),
        (r"moe/(shared|residual)/w3$", P(f, "model")),
        (r"moe/(shared|residual)/w2$", P("model", f)),
        # mamba
        (r"mamba/in_proj_[xz]$", P(f, "model")),            # (D, din)
        (r"mamba/conv_w$",       P("model", None)),         # (din, cw)
        (r"mamba/conv_b$",       P("model",)),
        (r"mamba/xp_(dt|b|c)$",  P("model", None)),         # (din, dtr|N)
        (r"mamba/dt_proj$",      P(None, "model")),         # (dtr, din)
        (r"mamba/dt_bias$",      P("model",)),
        (r"mamba/a_log$",        P("model", None)),         # (din, N)
        (r"mamba/d_skip$",       P("model",)),
        (r"mamba/out_proj$",     P("model", f)),            # (din, D)
        # rwkv
        (r"rwkv/w_[rkvg]$",      P(f, "model")),            # (D, D)
        (r"rwkv/w_o$",           P("model", f)),
        (r"rwkv/(mu_|u$|w_base|lora|ln_x)", P(None)),
        # norms, scalars, everything small: replicate
        (r"(norm|scale|bias)",   P(None)),
    ]


def _axis_len(mesh: Mesh, entry) -> int:
    """Product of mesh-axis sizes; 0 if any axis is absent from the mesh
    (callers drop the sharding entirely in that case)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            if a not in sizes:
                return 0
            n *= sizes[a]
        return n
    return sizes.get(entry, 0)


def _spec_for_path(path: str, shape, f, mesh: Optional[Mesh]) -> P:
    ndim = len(shape)
    for pat, spec in _param_rules(f):
        if re.search(pat, path):
            got = tuple(spec)
            if len(got) < ndim:       # stacked 'periods' leading axes
                got = (None,) * (ndim - len(got)) + got
            elif len(got) > ndim:
                got = got[-ndim:] if all(s is None for s in got[:len(got) - ndim]) else None
                if got is None:
                    raise ValueError(f"spec longer than ndim for {path}")
            if mesh is not None:      # drop absent axes / indivisible dims
                got = tuple(
                    a if (_axis_len(mesh, a) > 0
                          and shape[d] % _axis_len(mesh, a) == 0) else None
                    for d, a in enumerate(got))
            return P(*got)
    return P(*([None] * ndim))        # default: replicated


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(params_shape: Any, ctx: ParallelCtx):
    """PartitionSpec pytree mirroring a params (shape) pytree.

    Dims that don't divide their assigned mesh axes fall back to
    replicated (e.g. 8 KV heads on the 16-wide 'model' axis) — jit
    argument shardings require exact divisibility.
    """
    f = ctx.fsdp_axis

    def one(path, leaf):
        ps = _path_str(path)
        if ctx.vocab_sharded_embed and re.search(r"embed/table$", ps):
            spec = P("model", None)
            if leaf.shape[0] % _axis_len(ctx.mesh, "model") == 0:
                return spec
        return _spec_for_path(ps, leaf.shape, f, ctx.mesh)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape: Any, ctx: ParallelCtx):
    specs = param_pspecs(params_shape, ctx)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Input specs (activation shardings for jit in_shardings)
# ---------------------------------------------------------------------------

def batch_spec(ctx: ParallelCtx, ndim: int, batch_axis: int = 0) -> P:
    dp = ctx.dp_axes if ctx.shard_batch else ()
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    axes = [None] * ndim
    axes[batch_axis] = dp
    return P(*axes)


def cache_pspecs(cache_shape: Any, ctx: ParallelCtx):
    """PartitionSpec pytree for a decode cache (init_cache structure).

    Leaf layouts by key: k/v (.., B, S, KV, hd); conv (.., B, cw-1, din);
    h (.., B, din, N); state (.., B, H, hd, hd); *_shift (.., B, D).
    Period-stacked leaves carry one extra leading axis.
    """
    b = _resolve(ctx, "batch", "cache")
    s = _resolve(ctx, "kv_seq", "cache")
    kvh = _resolve(ctx, "kv_heads", "cache")

    def one(path, leaf):
        name = _path_str(path).split("/")[-1]
        nd = len(leaf.shape)
        if name in ("k", "v"):
            spec = (b, s, kvh, None)
        elif name in ("k_scale", "v_scale"):
            spec = (b, s, kvh)
        elif name == "conv":
            spec = (b, None, "model")
        elif name == "h":
            spec = (b, "model", None)
        elif name == "state":
            spec = (b, "model", None, None)
        elif name in ("tm_shift", "cm_shift"):
            spec = (b, None)
        else:
            spec = (None,) * nd
        if len(spec) < nd:                 # period-stack leading axes
            spec = (None,) * (nd - len(spec)) + tuple(spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def kv_cache_pspec(ctx: ParallelCtx, layout: Tuple[str, ...]) -> P:
    """layout names dims, e.g. ('layers','batch','kv_seq','kv_heads','head_dim')."""
    out = []
    for name in layout:
        if name == "batch":
            out.append(_resolve(ctx, "batch", "kv"))
        elif name == "kv_seq":
            out.append(_resolve(ctx, "kv_seq", "kv"))
        elif name == "kv_heads":
            out.append(_resolve(ctx, "kv_heads", "kv"))
        elif name in ("dinner",):
            out.append("model")
        else:
            out.append(None)
    return P(*out)
