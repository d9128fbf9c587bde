"""Top-k MoE with expert parallelism.

Routing decisions (top-k ids + gates + aux losses) are computed in plain
pjit-land (cheap, replicated over 'model').  The expert compute is dispatched
through ``shard_map``: experts are sharded over the ``'model'`` axis, tokens
stay local to their ``('pod','data')`` shard, and each expert shard
gathers the tokens routed to its experts (capacity-bounded), computes, and
scatter-adds its contribution; the partial outputs combine with a single
``psum`` over ``'model'`` — the same collective slot Megatron-TP MLPs use,
so EP costs no extra all-to-all here.

Expert weights are additionally FSDP-sharded over ``'data'`` on the hidden
dim; the shard does an explicit ``all_gather('data')`` (ZeRO-3 style) whose
transpose is the grads' reduce-scatter.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, init_mlp, apply_mlp
from repro.runtime.sharding import ParallelCtx, shard_act


def init_moe(rng, cfg: ModelConfig):
    D, E, Fe = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 6)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "w1": dense_init(ks[1], (E, D, Fe), dt),
        "w2": dense_init(ks[2], (E, Fe, D), dt),
    }
    if cfg.mlp_type == "swiglu":
        p["w3"] = dense_init(ks[3], (E, D, Fe), dt)
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.moe_d_ff)
    if cfg.moe_dense_residual:
        p["residual"] = init_mlp(ks[5], cfg, d_ff=cfg.dense_d_ff)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig, cap_factor: float) -> int:
    c = int(n_tokens * cfg.moe_top_k * cap_factor / cfg.moe_num_experts)
    c = max(8, c)
    c = -(-c // 8) * 8          # round up to 8
    return min(c, n_tokens)


def _expert_ffn(xg, w1, w3, w2, cfg: ModelConfig):
    """xg (E, C, D) -> (E, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", xg, w1)
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xg, w3)
    elif cfg.mlp_type == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _dispatch_compute_combine(x2, gate_mat, w1, w3, w2, *, cfg: ModelConfig,
                              e_offset, n_local: int, capacity: int):
    """x2 (T, D); gate_mat (T, E) combined gates (0 where not routed).

    Gathers up to ``capacity`` tokens per local expert (earliest-token
    priority), runs the expert FFN, scatter-adds gated outputs back.
    """
    T = x2.shape[0]
    local_gates = lax.dynamic_slice_in_dim(
        gate_mat, e_offset, n_local, axis=1)          # (T, E_loc)
    # earliest-first priority selection of up to C tokens per expert
    priority = jnp.where(local_gates.T > 0,
                         (T - jnp.arange(T, dtype=jnp.int32))[None, :], 0)
    score, idx = lax.top_k(priority, capacity)        # (E_loc, C)
    valid = (score > 0)
    xg = jnp.take(x2, idx.reshape(-1), axis=0).reshape(
        n_local, capacity, x2.shape[1])
    xg = jnp.where(valid[..., None], xg, 0).astype(x2.dtype)
    yg = _expert_ffn(xg, w1, w3, w2, cfg)             # (E_loc, C, D)
    slot_gate = jnp.take_along_axis(local_gates.T, idx, axis=1)
    yg = yg * jnp.where(valid, slot_gate, 0.0)[..., None].astype(yg.dtype)
    out = jnp.zeros_like(x2).at[idx.reshape(-1)].add(
        yg.reshape(-1, x2.shape[1]), mode="drop")
    # psum'd downstream: keep the wire dtype at bf16, not the f32
    # accumulator (halves the EP-combine collective bytes; §Perf Cell 2)
    return out.astype(x2.dtype)


def _moe_shard(w1, w3, w2, x, gate_mat, *, cfg: ModelConfig, capacity: int,
               fsdp_axis: Optional[str]):
    """Per-device body under shard_map.  x (B_loc, S, D); experts local.

    Training path: tokens stay data-sharded; the hidden dim of the local
    experts is ZeRO-3-gathered over 'data' (transpose = grads'
    reduce-scatter), compute runs at full hidden width, and expert
    contributions combine via one psum over 'model'.
    """
    if w3 is not None and w3.ndim != 3:   # scalar placeholder for non-gated
        w3 = None
    if fsdp_axis is not None:
        w1 = lax.all_gather(w1, fsdp_axis, axis=2, tiled=True)
        w2 = lax.all_gather(w2, fsdp_axis, axis=1, tiled=True)
        if w3 is not None:
            w3 = lax.all_gather(w3, fsdp_axis, axis=2, tiled=True)
    n_local = w1.shape[0]
    e_offset = lax.axis_index("model") * n_local
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    g2 = gate_mat.reshape(B * S, -1)
    out = _dispatch_compute_combine(
        x2, g2, w1, w3, w2, cfg=cfg, e_offset=e_offset,
        n_local=n_local, capacity=capacity)
    out = lax.psum(out, "model")
    return out.reshape(B, S, D)


def _moe_shard_tp(w1, w3, w2, x, gate_mat, *, cfg: ModelConfig,
                  capacity: int, dp_axes, hidden_axis: str):
    """Weight-stationary decode body: all-gather the (tiny) token batch
    across the data axes instead of gathering weights; each device
    computes its (E/model, hidden/data) weight tile at full strength and
    one psum over ('data','model') combines hidden partials + experts.
    Collective bytes per layer: O(tokens·D), independent of expert size.
    """
    if w3 is not None and w3.ndim != 3:
        w3 = None
    B_loc, S, D = x.shape
    if dp_axes:
        x = lax.all_gather(x, dp_axes, axis=0, tiled=True)
        gate_mat = lax.all_gather(gate_mat, dp_axes, axis=0, tiled=True)
    B, S, D = x.shape
    n_local = w1.shape[0]
    e_offset = lax.axis_index("model") * n_local
    x2 = x.reshape(B * S, D)
    g2 = gate_mat.reshape(B * S, -1)
    out = _dispatch_compute_combine(
        x2, g2, w1, w3, w2, cfg=cfg, e_offset=e_offset,
        n_local=n_local, capacity=capacity)
    # hidden dim was sharded -> partial sums over 'data'; experts over 'model'
    out = lax.psum(out, (hidden_axis, "model") if dp_axes else ("model",))
    out = out.reshape(B, S, D)
    if dp_axes:
        # slice back this device's batch rows
        idx = lax.axis_index(dp_axes[0])
        for a in dp_axes[1:]:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        out = lax.dynamic_slice_in_dim(out, idx * B_loc, B_loc, axis=0)
    return out


def apply_moe(p, x, cfg: ModelConfig, ctx: Optional[ParallelCtx]):
    """x (B, S, D) -> (out (B, S, D), aux losses dict)."""
    B, S, D = x.shape
    E, K = cfg.moe_num_experts, cfg.moe_top_k

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_logits, ids = lax.top_k(logits, K)            # (B,S,K)
    gates = jax.nn.softmax(top_logits, axis=-1)

    # aux: load-balance (Switch-style) + router z-loss
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)       # (B,S,K,E)
    tok_frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))  # (E,)
    prob_frac = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(tok_frac * prob_frac)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_load_balance": lb_loss, "moe_z_loss": z_loss}

    gate_mat = jnp.sum(onehot * gates[..., None], axis=2)    # (B,S,E)
    gate_mat = gate_mat.astype(jnp.float32)

    w3 = p.get("w3")
    e_ok = (ctx is not None and "model" in ctx.axis_names
            and E % ctx.axis_size("model") == 0)
    if ctx is None or not e_ok:
        # no EP (single device, or experts don't divide the model axis —
        # e.g. reduced test configs): dispatch locally, XLA partitions
        cap = _capacity(B * S, cfg, 1.25)
        out = _dispatch_compute_combine(
            x.reshape(B * S, D), gate_mat.reshape(B * S, E),
            p["w1"], w3, p["w2"], cfg=cfg, e_offset=0, n_local=E,
            capacity=cap)
        out = out.reshape(B, S, D)
    else:
        dp = ctx.dp_axes if ctx.shard_batch else ()
        dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
        n_dp = 1
        for a in dp:
            n_dp *= ctx.axis_size(a)
        hidden = "data" if "data" in ctx.axis_names else None
        h_ok = hidden is not None and cfg.moe_d_ff % ctx.axis_size("data") == 0
        hspec = hidden if h_ok else None
        w_specs = (P("model", None, hspec),
                   P("model", None, hspec) if w3 is not None else P(),
                   P("model", hspec, None))
        if ctx.moe_decode_tp and h_ok:
            # weight-stationary: gather tokens, psum hidden partials
            cap = _capacity(B * S, cfg, ctx.moe_capacity_factor)
            fn = functools.partial(_moe_shard_tp, cfg=cfg, capacity=cap,
                                   dp_axes=dp, hidden_axis=hidden)
        else:
            t_local = max(1, (B // n_dp) * S)
            cap = _capacity(t_local, cfg, ctx.moe_capacity_factor)
            fn = functools.partial(_moe_shard, cfg=cfg, capacity=cap,
                                   fsdp_axis=hspec)
        out = jax.shard_map(
            fn, mesh=ctx.mesh,
            in_specs=(w_specs[0], w_specs[1], w_specs[2],
                      P(dp_spec, None, None), P(dp_spec, None, None)),
            out_specs=P(dp_spec, None, None),
            check_vma=False,
        )(p["w1"], w3 if w3 is not None else jnp.zeros((), x.dtype),
          p["w2"], x, gate_mat)

    if cfg.moe_shared_expert:
        out = out + apply_mlp(p["shared"], x, cfg, ctx)
    if cfg.moe_dense_residual:
        out = out + apply_mlp(p["residual"], x, cfg, ctx)
    return shard_act(out, ("batch", "seq", "embed"), ctx), aux
