"""Mixture-of-Experts channel mixer.

Design (TPU-native, see DESIGN.md §5):

* Tokens are reshaped into G groups aligned with the dp sharding, so all
  routing / dispatch-index math and the dispatch gathers are *local* to a
  data shard (GSPMD never needs to move tokens for dispatch).
* Expert weights are sharded over the ``model`` axis (expert parallelism).
  Expert compute runs inside a ``shard_map`` over {"model"}: each shard
  gathers the tokens routed to *its* experts (local — tokens are replicated
  across the model axis), runs its expert FFNs, scatter-gathers the weighted
  outputs back to token positions, and one ``psum`` over the model axis
  combines partial token outputs. Collective cost per MoE layer is one
  all-reduce of (tokens × d_model) — identical to dense-FFN Megatron TP and
  independent of n_experts.
* Capacity: per-group per-expert slots C = ceil(Tg·K/E · capacity_factor);
  overflow tokens are dropped (zero combine weight) — GShard/Switch
  semantics. Tests use a high factor to validate against the dense oracle.
* Decode note: when Tg·K < E the slot tensor is padded up to E slots/group.
  The padding wastes MXU flops but moves no extra bytes; decode MoE is
  weight-bandwidth-bound, so the memory roofline term is unaffected (the
  MODEL_FLOPS/HLO_FLOPS ratio in EXPERIMENTS.md surfaces the waste).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.basic import ffn, ffn_specs
from repro.nn.config import MoEConfig
from repro.nn.param import ParamSpec
from repro.nn.sharding import ShardCtx


def moe_specs(cfg: MoEConfig, d_model: int, dtype) -> dict:
    e, f = cfg.n_experts, cfg.d_ff_expert
    out = {
        "router": ParamSpec((d_model, e), jnp.float32, (None, None), scale=0.02),
        "w_gate": ParamSpec((e, d_model, f), dtype, ("expert", "fsdp", None)),
        "w_up": ParamSpec((e, d_model, f), dtype, ("expert", "fsdp", None)),
        "w_down": ParamSpec((e, f, d_model), dtype, ("expert", None, "fsdp")),
    }
    if cfg.router_fn == "sigmoid":
        # deepseek-v3 aux-loss-free balancing bias (updated out-of-band)
        out["router_bias"] = ParamSpec((e,), jnp.float32, (None,), init="zeros")
    if cfg.n_shared:
        d_sh = cfg.d_ff_shared or cfg.d_ff_expert * cfg.n_shared
        out["shared"] = ffn_specs(d_model, d_sh, dtype, act="swiglu")
    return out


def _route(p, cfg: MoEConfig, x):
    """x: (G, Tg, D) -> weights (G,Tg,K) f32, idx (G,Tg,K) i32, aux scalar."""
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32), p["router"])
    if cfg.router_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"][None, None, :]
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_scale:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * p_e
    probs_mean = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=(0, 1))
    counts = (
        jnp.zeros((cfg.n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    )
    frac = counts / (idx.size + 1e-9)
    aux = cfg.n_experts * jnp.sum(frac * probs_mean) * cfg.aux_loss_coef
    return w, idx, aux


def _dispatch_indices_1g(top_k: int, n_experts: int, capacity: int, idx):
    """Per-group dispatch plan. idx: (Tg, K) expert choices.

    Returns:
      slot_src: (E*C,) source-token index per slot (Tg = dummy/empty)
      tok_slot: (Tg, K) slot id per (token, choice) (E*C = dropped)
    """
    t, k = idx.shape
    e, cap = n_experts, capacity
    flat_e = idx.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[e_sorted]
    keep = rank < cap
    slot = jnp.where(keep, e_sorted * cap + rank, e * cap)
    slot_src = jnp.full((e * cap + 1,), t, jnp.int32)
    slot_src = slot_src.at[slot].set(jnp.where(keep, tok_sorted, t))[:-1]
    tok_slot_flat = jnp.full((t * k,), e * cap, jnp.int32)
    tok_slot_flat = tok_slot_flat.at[order].set(
        jnp.where(keep, slot, e * cap)
    )
    return slot_src, tok_slot_flat.reshape(t, k)


def _expert_ffn(pw, xe):
    """xe: (G, E_loc, C, D) -> through per-expert SwiGLU."""
    h = jnp.einsum("gecd,edf->gecf", xe, pw["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", xe, pw["w_up"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(xe.dtype) * u
    return jnp.einsum("gecf,efd->gecd", h, pw["w_down"])


def _moe_body(pw, cfg, xg, w, slot_src, tok_slot, cap, e_lo, e_local):
    g, t, d = xg.shape
    lo = e_lo * cap
    span = e_local * cap
    src = jax.lax.dynamic_slice_in_dim(slot_src, lo, span, axis=1)  # (G, span)
    x_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    xe = jnp.take_along_axis(x_pad, src[..., None], axis=1)  # (G, span, D)
    xe = xe.reshape(g, e_local, cap, d)
    ye = _expert_ffn(pw, xe).reshape(g, span, d)
    flat_slot = tok_slot.reshape(g, t * cfg.top_k)
    local = (flat_slot >= lo) & (flat_slot < lo + span)
    loc_slot = jnp.where(local, flat_slot - lo, span)
    y_pad = jnp.concatenate([ye, jnp.zeros((g, 1, d), ye.dtype)], axis=1)
    contrib = jnp.take_along_axis(y_pad, loc_slot[..., None], axis=1)
    contrib = contrib.reshape(g, t, cfg.top_k, d)
    wk = jnp.where(
        local.reshape(g, t, cfg.top_k), w.astype(jnp.float32), 0.0
    ).astype(xg.dtype)
    return jnp.einsum("gtkd,gtk->gtd", contrib, wk)


def moe_apply(ctx: ShardCtx, p, cfg: MoEConfig, x):
    """x: (B, S, D) -> (out, aux_loss)."""
    b, s, d = x.shape
    t_total = b * s
    dp = ctx.dp_size()
    n_groups = dp if t_total % dp == 0 else 1
    tg = t_total // n_groups
    xg = x.reshape(n_groups, tg, d)
    xg = ctx.constrain(xg, "dp", None, None)

    w, idx, aux = _route(p, cfg, xg)
    cap = int(
        max(1, round(tg * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    )
    slot_src, tok_slot = jax.vmap(
        lambda i: _dispatch_indices_1g(cfg.top_k, cfg.n_experts, cap, i)
    )(idx)

    e = cfg.n_experts
    tp = ctx.tp_size()
    use_ep = ctx.mesh is not None and tp > 1 and e % tp == 0
    pw = {"w_gate": p["w_gate"], "w_up": p["w_up"], "w_down": p["w_down"]}

    if use_ep:
        out = _moe_shardmap(ctx, pw, cfg, xg, w, slot_src, tok_slot, cap, e // tp)
    else:
        out = _moe_body(pw, cfg, xg, w, slot_src, tok_slot, cap, 0, e)

    out = out.reshape(b, s, d)
    if cfg.n_shared:
        out = out + ffn(ctx, p["shared"], x, act="swiglu")
    return ctx.constrain(out, "dp", None, None), aux


def _moe_shardmap(ctx, pw, cfg, xg, w, slot_src, tok_slot, cap, e_local):
    """Expert-parallel path: experts sharded over the model axis, tokens
    sharded over dp (groups are dp-aligned), partial token outputs
    psum-combined over the model axis.

    Fully-manual over every mesh axis — half-manual (auto-dp) shard_maps
    trip an XLA SPMD-partitioner check failure at 512 devices. The entry
    reshard of the expert weights (FSDP dim gathered on entry, transposed
    to a reduce-scatter in the backward) IS the explicit ZeRO-3 gather.
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    axis = ctx.cfg.mesh_axes("model")[0]
    n_groups = xg.shape[0]
    dp_axes = [
        a for a in ctx.cfg.mesh_axes("dp") if a in mesh.shape
    ]
    kept, prod = [], 1
    for a in dp_axes:
        if n_groups % (prod * mesh.shape[a]) == 0:
            kept.append(a)
            prod *= mesh.shape[a]
    dp = tuple(kept) if kept else None

    def inner(pw_, xg_, w_, slot_src_, tok_slot_):
        eidx = jax.lax.axis_index(axis)
        out = _moe_body(
            pw_, cfg, xg_, w_, slot_src_, tok_slot_, cap, eidx * e_local, e_local
        )
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(axis), P(dp, None, None), P(dp, None, None),
            P(dp, None), P(dp, None, None),
        ),
        out_specs=P(dp, None, None),
        axis_names=set(mesh.axis_names),
    )(pw, xg, w, slot_src, tok_slot)
