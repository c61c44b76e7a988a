"""Composable language-model assembly.

A model is `prefix layers (unrolled) + superblock × n_repeat (lax.scan)`,
optionally with an encoder stack (enc-dec) and a modality-frontend stub.
Scan-over-superblocks keeps the HLO O(1) in depth — a 80-layer qwen1.5-110b
and a 24-layer xlstm-350m compile to similarly-sized modules, which is what
makes 40 (arch × shape) dry-run cells tractable.

Steps exposed:
  * ``loss_and_aux``   — train forward (+ MoE aux, + MTP loss)
  * ``prefill``        — returns logits + populated caches
  * ``decode``         — one token with a seq_len KV cache (serve_step)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import attention as att
from repro.nn import basic, moe as moe_mod, ssm, xlstm as xl
from repro.nn.config import LayerSpec, ModelConfig
from repro.nn.param import ParamSpec, stack_tree
from repro.nn.sharding import ShardCtx

from repro.nn import runtime as _runtime

# ----------------------------------------------------------- layer specs


def layer_specs(spec: LayerSpec, d_model: int, dtype, norm_eps: float) -> dict:
    p: dict[str, Any] = {"norm1": basic.rmsnorm_specs(d_model)}
    if spec.kind == "attn":
        if spec.attn.kind == "mla":
            p["mixer"] = att.mla_specs(spec.attn, d_model, dtype)
        else:
            p["mixer"] = att.gqa_specs(spec.attn, d_model, dtype)
    elif spec.kind == "mamba":
        p["mixer"] = ssm.mamba_specs(spec.mamba, d_model, dtype)
    elif spec.kind == "mlstm":
        p["mixer"] = xl.mlstm_specs(spec.xlstm, d_model, dtype)
    elif spec.kind == "slstm":
        p["mixer"] = xl.slstm_specs(spec.xlstm, d_model, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.cross_attn:
        p["cross_norm"] = basic.rmsnorm_specs(d_model)
        p["cross"] = att.gqa_specs(
            dataclasses.replace(spec.attn, rope_kind="none"), d_model, dtype
        )
    if spec.moe is not None:
        p["norm2"] = basic.rmsnorm_specs(d_model)
        p["moe"] = moe_mod.moe_specs(spec.moe, d_model, dtype)
    elif spec.d_ff:
        p["norm2"] = basic.rmsnorm_specs(d_model)
        p["ffn"] = basic.ffn_specs(d_model, spec.d_ff, dtype, spec.ffn_act)
    return p


def layer_cache_specs(
    spec: LayerSpec, d_model: int, batch: int, s_cache: int, dtype,
    enc_len: int = 0, kv_quant: bool = False,
) -> dict:
    out: dict[str, Any] = {}
    if spec.kind == "attn":
        if spec.attn.kind == "mla":
            out["mixer"] = att.mla_cache_specs(spec.attn, batch, s_cache, dtype)
        else:
            out["mixer"] = att.gqa_cache_specs(
                spec.attn, batch, s_cache, dtype, quant=kv_quant
            )
    elif spec.kind == "mamba":
        out["mixer"] = ssm.mamba_cache_specs(spec.mamba, d_model, batch)
    elif spec.kind == "mlstm":
        out["mixer"] = xl.mlstm_cache_specs(spec.xlstm, d_model, batch)
    elif spec.kind == "slstm":
        out["mixer"] = xl.slstm_cache_specs(spec.xlstm, d_model, batch)
    if spec.cross_attn:
        kv, dh = spec.attn.n_kv_heads, spec.attn.head_dim
        shp = (batch, enc_len, kv, dh)
        axes = ("dp", "seq" if batch == 1 else "kv_seq", None, None)
        out["cross_kv"] = {
            "k": ParamSpec(shp, dtype, axes, init="zeros"),
            "v": ParamSpec(shp, dtype, axes, init="zeros"),
        }
    return out


def apply_layer(
    ctx: ShardCtx,
    spec: LayerSpec,
    p,
    x,
    positions,
    *,
    cache=None,
    cache_pos=None,
    causal: bool = True,
    enc_out=None,
    norm_eps: float = 1e-6,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = basic.rmsnorm(p["norm1"], x, norm_eps)
    mix_cache = cache.get("mixer") if cache else None
    if spec.kind == "attn":
        if spec.attn.kind == "mla":
            y, new_mix = att.mla_apply(
                ctx, p["mixer"], spec.attn, h, positions,
                cache=mix_cache, cache_pos=cache_pos, eps=norm_eps,
            )
        else:
            if not causal and mix_cache is None:
                # encoder self-attention: full bidirectional
                y, new_mix = _bidir_attn(ctx, p["mixer"], spec.attn, h, positions)
            else:
                y, new_mix = att.gqa_apply(
                    ctx, p["mixer"], spec.attn, h, positions,
                    cache=mix_cache, cache_pos=cache_pos,
                )
    elif spec.kind == "mamba":
        y, new_mix = ssm.mamba_apply(ctx, p["mixer"], spec.mamba, h, cache=mix_cache)
    elif spec.kind == "mlstm":
        y, new_mix = xl.mlstm_apply(ctx, p["mixer"], spec.xlstm, h, cache=mix_cache)
    elif spec.kind == "slstm":
        y, new_mix = xl.slstm_apply(ctx, p["mixer"], spec.xlstm, h, cache=mix_cache)
    else:
        raise ValueError(spec.kind)
    # named for the "save_outs" remat policy: saving the two post-AR layer
    # outputs lets backward recompute skip re-running the matmul+all-reduce
    # (§Perf: trades ~2 activations/layer of memory for 1/3 of TP traffic)
    y = jax.ad_checkpoint.checkpoint_name(y, "mixer_out")
    x = x + y
    new_cache: dict[str, Any] = {"mixer": new_mix} if new_mix is not None else {}

    if spec.cross_attn:
        hc = basic.rmsnorm(p["cross_norm"], x, norm_eps)
        if cache is not None and "cross_kv" in cache:
            kvp = (cache["cross_kv"]["k"], cache["cross_kv"]["v"])
        else:
            kvp = att.cross_kv(
                ctx, {"wk": p["cross"]["wk"], "wv": p["cross"]["wv"]},
                spec.attn, enc_out,
            )
        yc, _ = att.gqa_apply(
            ctx, p["cross"],
            dataclasses.replace(spec.attn, rope_kind="none"),
            hc, positions, kv_override=kvp,
        )
        x = x + yc
        new_cache["cross_kv"] = {"k": kvp[0], "v": kvp[1]}

    if spec.moe is not None:
        h2 = basic.rmsnorm(p["norm2"], x, norm_eps)
        y2, aux = moe_mod.moe_apply(ctx, p["moe"], spec.moe, h2)
        x = x + jax.ad_checkpoint.checkpoint_name(y2, "ffn_out")
    elif spec.d_ff:
        h2 = basic.rmsnorm(p["norm2"], x, norm_eps)
        y2 = basic.ffn(ctx, p["ffn"], h2, spec.ffn_act)
        x = x + jax.ad_checkpoint.checkpoint_name(y2, "ffn_out")
    return x, new_cache, aux


def _bidir_attn(ctx, p, cfg, x, positions):
    """Encoder self-attention (no causal mask)."""
    import math

    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = att._split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), h, dh)
    k = att._split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), kv, dh)
    v = att._split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), kv, dh)
    if cfg.rope_kind != "none":
        q = basic.apply_rope(cfg, q, positions)
        k = basic.apply_rope(cfg, k, positions)
    if s > att.FLASH_THRESHOLD:
        from repro.nn.flash import sdpa_flash

        out = sdpa_flash(
            q, k, v, 1.0 / math.sqrt(dh), causal=False,
            chunk=min(att.flash_chunk(s), s),
        )
    else:
        mask = jnp.ones((b, s, s), bool)
        out = att._sdpa(ctx, q, k, v, mask, 1.0 / math.sqrt(dh))
    out = out.reshape(b, s, h * dh)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return ctx.constrain(y, "dp", None, None), None


# ----------------------------------------------------------- model


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---------------- parameter tree

    def param_specs(self) -> dict:
        cfg = self.cfg
        dt = cfg.pdt
        tree: dict[str, Any] = {
            "embed": basic.embedding_specs(cfg.vocab_size, cfg.d_model, dt),
            "final_norm": basic.rmsnorm_specs(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            tree["head"] = {
                "table": ParamSpec(
                    (cfg.vocab_size, cfg.d_model), dt, ("model", "fsdp"),
                    scale=0.02,
                )
            }
        if cfg.prefix:
            tree["prefix"] = [
                layer_specs(sp, cfg.d_model, dt, cfg.norm_eps) for sp in cfg.prefix
            ]
        if cfg.blocks and cfg.n_repeat:
            one = {
                f"l{i}": layer_specs(sp, cfg.d_model, dt, cfg.norm_eps)
                for i, sp in enumerate(cfg.blocks)
            }
            tree["blocks"] = (
                stack_tree(one, cfg.n_repeat) if cfg.n_repeat > 1 else one
            )
        if cfg.enc_dec:
            enc_one = {
                f"l{i}": layer_specs(sp, cfg.d_model, dt, cfg.norm_eps)
                for i, sp in enumerate(cfg.enc_blocks)
            }
            tree["enc_blocks"] = (
                stack_tree(enc_one, cfg.enc_repeat)
                if cfg.enc_repeat > 1 else enc_one
            )
            tree["enc_norm"] = basic.rmsnorm_specs(cfg.d_model)
        if cfg.frontend:
            tree["frontend_proj"] = {
                "w": ParamSpec((cfg.d_model, cfg.d_model), dt, ("fsdp", "model"))
            }
        if cfg.mtp:
            mtp_layer = cfg.blocks[-1]
            tree["mtp"] = {
                "norm_h": basic.rmsnorm_specs(cfg.d_model),
                "norm_e": basic.rmsnorm_specs(cfg.d_model),
                "proj": ParamSpec(
                    (2 * cfg.d_model, cfg.d_model), dt, ("fsdp", "model")
                ),
                "block": layer_specs(mtp_layer, cfg.d_model, dt, cfg.norm_eps),
            }
        return tree

    # ---------------- forward pieces

    def _embed(self, ctx, params, tokens):
        return _sharded_embed(ctx, params["embed"]["table"], tokens)

    def _logits(self, ctx, params, x):
        cfg = self.cfg
        x = basic.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        table = (
            params["embed"]["table"] if cfg.tie_embeddings
            else params["head"]["table"]
        )
        logits = jnp.einsum("...d,vd->...v", x, table)
        logits = ctx.constrain(logits, "dp", None, "model")
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits.astype(jnp.float32) / c) * c
        return logits

    def _positions(self, tokens, offset=0):
        b, s = tokens.shape[:2]
        pos = offset + jnp.arange(s, dtype=jnp.int32)[None, :]
        return jnp.broadcast_to(pos, (b, s))

    def _run_stack(
        self, ctx, params, x, positions, *, caches=None, cache_pos=None,
        causal=True, enc_out=None, remat: str = "none",
    ):
        """prefix (unrolled) + scan over stacked superblocks."""
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_prefix_caches = []
        if cfg.prefix:
            for i, sp in enumerate(cfg.prefix):
                c_i = caches["prefix"][i] if caches else None
                x, nc, aux = apply_layer(
                    ctx, sp, params["prefix"][i], x, positions,
                    cache=c_i, cache_pos=cache_pos, causal=causal,
                    enc_out=enc_out, norm_eps=cfg.norm_eps,
                )
                aux_total += aux
                new_prefix_caches.append(nc)

        want_cache = caches is not None
        if cfg.blocks and cfg.n_repeat:
            block_params = params["blocks"]
            block_caches = caches["blocks"] if caches else None

            def superblock(x, p_sb, c_sb):
                new_c = {}
                aux_sb = jnp.zeros((), jnp.float32)
                for i, sp in enumerate(self.cfg.blocks):
                    c_i = c_sb.get(f"l{i}") if c_sb else None
                    x, nc, aux = apply_layer(
                        ctx, sp, p_sb[f"l{i}"], x, positions,
                        cache=c_i, cache_pos=cache_pos, causal=causal,
                        enc_out=enc_out, norm_eps=self.cfg.norm_eps,
                    )
                    if want_cache:
                        new_c[f"l{i}"] = nc
                    aux_sb += aux
                return x, new_c, aux_sb

            if cfg.n_repeat > 1 and block_caches is not None:
                # decode/refill with existing caches: the stacked cache
                # tree rides the scan CARRY (while-loop carries alias in
                # place) instead of xs/ys, which would copy the whole
                # cache per layer (§Perf iteration 3: 2.5x decode temp)
                def body_c(carry, xs):
                    x, aux_acc, cache_all = carry
                    i, p_sb = xs
                    c_sb = jax.tree.map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, i, 0, keepdims=False
                        ),
                        cache_all,
                    )
                    x, new_c, aux_sb = superblock(x, p_sb, c_sb)
                    cache_all = jax.tree.map(
                        lambda buf, nc: jax.lax.dynamic_update_index_in_dim(
                            buf, nc.astype(buf.dtype), i, 0
                        ),
                        cache_all, new_c,
                    )
                    return (x, aux_acc + aux_sb, cache_all), None

                (x, aux_sb, new_block_caches), _ = jax.lax.scan(
                    body_c, (x, aux_total, block_caches),
                    (jnp.arange(cfg.n_repeat), block_params),
                    unroll=_runtime.unroll_for(cfg.n_repeat),
                )
                aux_total = aux_sb
            elif cfg.n_repeat > 1:
                def body(carry, p_sb):
                    x, aux_acc = carry
                    x, new_c, aux_sb = superblock(x, p_sb, None)
                    return (x, aux_acc + aux_sb), new_c

                if remat != "none":
                    if remat == "dots":
                        policy = (jax.checkpoint_policies
                                  .dots_with_no_batch_dims_saveable)
                    elif remat == "save_outs":
                        policy = jax.checkpoint_policies.save_only_these_names(
                            "mixer_out", "ffn_out"
                        )
                    else:
                        policy = None
                    body = jax.checkpoint(body, policy=policy)
                (x, aux_sb), new_block_caches = jax.lax.scan(
                    body, (x, aux_total), block_params,
                    unroll=_runtime.unroll_for(cfg.n_repeat),
                )
                aux_total = aux_sb
            else:
                sb = superblock
                if remat != "none":
                    if remat == "dots":
                        policy = (jax.checkpoint_policies
                                  .dots_with_no_batch_dims_saveable)
                    elif remat == "save_outs":
                        policy = jax.checkpoint_policies.save_only_these_names(
                            "mixer_out", "ffn_out"
                        )
                    else:
                        policy = None
                    sb = jax.checkpoint(superblock, policy=policy)
                x, new_block_caches, aux_sb = sb(
                    x, block_params, block_caches
                )
                aux_total = aux_total + aux_sb
        else:
            new_block_caches = None

        new_caches = None
        if caches is not None:
            new_caches = {"blocks": new_block_caches}
            if cfg.prefix:
                new_caches["prefix"] = new_prefix_caches
        return x, new_caches, aux_total

    def _encode(self, ctx, params, enc_emb):
        """Encoder stack over precomputed frontend embeddings (audio)."""
        cfg = self.cfg
        x = enc_emb
        positions = self._positions(enc_emb[..., 0])

        def superblock(x, p_sb):
            for i, sp in enumerate(cfg.enc_blocks):
                x, _, _ = apply_layer(
                    ctx, sp, p_sb[f"l{i}"], x, positions,
                    causal=False, norm_eps=cfg.norm_eps,
                )
            return x

        if cfg.enc_repeat > 1:
            def body(x, p_sb):
                return superblock(x, p_sb), None
            x, _ = jax.lax.scan(
                body, x, params["enc_blocks"],
                unroll=_runtime.unroll_for(cfg.enc_repeat),
            )
        else:
            x = superblock(x, params["enc_blocks"])
        return basic.rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    # ---------------- public steps

    def loss_and_aux(self, ctx, params, batch, remat: str = "none"):
        """batch: tokens (B,S), labels (B,S), optional frontend_emb,
        frontend_mask, positions (mrope), enc_emb."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(ctx, params, tokens)
        if cfg.frontend == "vision":
            fe = jnp.einsum(
                "bsd,de->bse", batch["frontend_emb"], params["frontend_proj"]["w"]
            )
            x = jnp.where(batch["frontend_mask"][..., None], fe, x)
        positions = batch.get("positions")
        if positions is None:
            positions = self._positions(tokens)
        enc_out = None
        if cfg.enc_dec:
            enc_out = self._encode(ctx, params, batch["enc_emb"])
        x, _, aux = self._run_stack(
            ctx, params, x, positions, enc_out=enc_out, remat=remat
        )
        loss = self._loss_from_hidden(ctx, params, x, batch["labels"])
        if cfg.mtp:
            loss = loss + 0.3 * self._mtp_loss(ctx, params, x, tokens, batch)
        return loss + aux, {"aux": aux}

    def _loss_from_hidden(self, ctx, params, x, labels):
        """Cross-entropy from final hidden states. Without TP the fused
        chunked-vocab loss avoids materialising (tokens x vocab) logits
        (§Perf iteration 5); with TP the Megatron vocab-sharded path runs."""
        cfg = self.cfg
        table = (
            params["embed"]["table"] if cfg.tie_embeddings
            else params["head"]["table"]
        )
        if ctx.mesh is None or ctx.tp_size() == 1:
            from repro.nn.xent import chunked_xent

            xn = basic.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            t = xn.shape[0] * xn.shape[1]
            return chunked_xent(
                xn.reshape(t, cfg.d_model), table, labels.reshape(t),
                16384, cfg.logit_softcap,
            )
        logits = self._logits(ctx, params, x)
        return _sharded_xent(ctx, logits, labels)

    def _mtp_loss(self, ctx, params, h, tokens, batch):
        """DeepSeek-V3 multi-token prediction: predict t+2 from (h_t, emb_{t+1})."""
        cfg = self.cfg
        p = params["mtp"]
        emb_next = self._embed(ctx, params, jnp.roll(tokens, -1, axis=1))
        z = jnp.concatenate(
            [basic.rmsnorm(p["norm_h"], h, cfg.norm_eps),
             basic.rmsnorm(p["norm_e"], emb_next, cfg.norm_eps)], axis=-1
        )
        z = jnp.einsum("bsd,de->bse", z, p["proj"])
        positions = self._positions(tokens)
        z, _, _ = (
            apply_layer(
                ctx, cfg.blocks[-1], p["block"], z, positions,
                norm_eps=cfg.norm_eps,
            )
        )
        labels2 = jnp.roll(batch["labels"], -1, axis=1)
        return self._loss_from_hidden(ctx, params, z, labels2)

    def prefill(self, ctx, params, batch, s_cache: Optional[int] = None):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(ctx, params, tokens)
        if cfg.frontend == "vision":
            fe = jnp.einsum(
                "bsd,de->bse", batch["frontend_emb"], params["frontend_proj"]["w"]
            )
            x = jnp.where(batch["frontend_mask"][..., None], fe, x)
        positions = batch.get("positions")
        if positions is None:
            positions = self._positions(tokens)
        enc_out = None
        if cfg.enc_dec:
            enc_out = self._encode(ctx, params, batch["enc_emb"])
        # prefill runs cache-less (train-path mixers) and returns final
        # mixer states; attention K/V are emitted by the mixers themselves.
        caches = self._empty_cache_tree()
        x, new_caches, _ = self._run_stack(
            ctx, params, x, positions, caches=caches, cache_pos=None,
            causal=True, enc_out=enc_out,
        )
        logits = self._logits(ctx, params, x[:, -1:, :])
        return logits, new_caches

    def prefill_flops(self, tokens: int) -> float:
        """Forward prefill FLOPs over ``tokens`` tokens (2·N_active·T,
        the roofline model). The JudgePipeline derives the judge's
        token-equivalent serving cost from this — see DESIGN.md §14."""
        from repro.launch.roofline import model_flops

        return model_flops(self.cfg, "prefill", tokens)

    def _empty_cache_tree(self):
        cfg = self.cfg
        tree: dict[str, Any] = {"blocks": None}
        if cfg.prefix:
            tree["prefix"] = [None] * len(cfg.prefix)
        return tree

    def decode(self, ctx, params, tokens, caches, pos, enc_out=None,
               positions=None):
        """tokens: (B,1); caches from cache_specs; pos: scalar write index."""
        cfg = self.cfg
        x = self._embed(ctx, params, tokens)
        if positions is None:
            b = tokens.shape[0]
            positions = jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32)[None, None], (b, 1)
            )
        x, new_caches, _ = self._run_stack(
            ctx, params, x, positions, caches=caches, cache_pos=pos,
            enc_out=enc_out,
        )
        logits = self._logits(ctx, params, x)
        return logits, new_caches

    # ---------------- cache tree

    def cache_specs(self, batch: int, s_cache: int, enc_len: int = 0,
                    kv_quant: bool = False) -> dict:
        cfg = self.cfg
        dt = cfg.pdt
        tree: dict[str, Any] = {}
        if cfg.prefix:
            tree["prefix"] = [
                layer_cache_specs(sp, cfg.d_model, batch, s_cache, dt,
                                  enc_len, kv_quant)
                for sp in cfg.prefix
            ]
        one = {
            f"l{i}": layer_cache_specs(
                sp, cfg.d_model, batch, s_cache, dt, enc_len, kv_quant
            )
            for i, sp in enumerate(cfg.blocks)
        }
        tree["blocks"] = stack_tree(one, cfg.n_repeat) if cfg.n_repeat > 1 else one
        return tree


# ----------------------------------------------------------- shard helpers


def _dp_entry(ctx: ShardCtx, dim: int):
    """Mesh-axis tuple to shard a batch dim of the given size, or None."""
    axes = [
        a for a in ctx.cfg.mesh_axes("dp") if a in ctx.mesh.shape
    ]
    kept, prod = [], 1
    for a in axes:
        if dim % (prod * ctx.mesh.shape[a]) == 0:
            kept.append(a)
            prod *= ctx.mesh.shape[a]
    return tuple(kept) if kept else None


def _sharded_embed(ctx: ShardCtx, table, tokens):
    """Megatron vocab-parallel embedding: masked local gather + psum.

    Fully-manual shard_map over every mesh axis — the half-manual (auto-dp)
    variant trips an XLA SPMD-partitioner check failure at 512 devices
    (b/433785288-adjacent); fully-manual regions bypass GSPMD entirely.
    """
    if ctx.mesh is None or ctx.tp_size() == 1 or \
            table.shape[0] % ctx.tp_size() != 0:
        out = jnp.take(table, tokens, axis=0)
        return ctx.constrain(out, "dp", None, None)
    axis = ctx.cfg.mesh_axes("model")[0]
    v_local = table.shape[0] // ctx.tp_size()
    dp = _dp_entry(ctx, tokens.shape[0])

    def inner(tbl, tok):
        lo = jax.lax.axis_index(axis) * v_local
        loc = tok - lo
        ok = (loc >= 0) & (loc < v_local)
        loc = jnp.clip(loc, 0, v_local - 1)
        out = jnp.take(tbl, loc, axis=0) * ok[..., None].astype(tbl.dtype)
        return jax.lax.psum(out, axis)

    out = jax.shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(P(axis, None), P(dp, None)),
        out_specs=P(dp, None, None),
        axis_names=set(ctx.mesh.axis_names),
    )(table, tokens)
    return ctx.constrain(out, "dp", None, None)


def _sharded_xent(ctx: ShardCtx, logits, labels):
    """Cross-entropy over vocab-sharded logits without materialising the
    gathered vocab axis (Megatron-style: local max/sumexp + label pick).
    Fully-manual shard_map (see _sharded_embed note)."""
    if ctx.mesh is None or ctx.tp_size() == 1 or \
            logits.shape[-1] % ctx.tp_size() != 0:
        lgf = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(lgf, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lgf - m), axis=-1)) + m[..., 0]
        picked = jnp.take_along_axis(lgf, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
    axis = ctx.cfg.mesh_axes("model")[0]
    v_local = logits.shape[-1] // ctx.tp_size()
    dp = _dp_entry(ctx, logits.shape[0])
    n_tokens = logits.shape[0] * logits.shape[1]

    def inner(lg, lb):
        lgf = lg.astype(jnp.float32)
        # stabiliser max carries no gradient (it cancels in softmax algebra)
        local_max = jax.lax.stop_gradient(jnp.max(lgf, axis=-1))
        gmax = jax.lax.pmax(local_max, axis)
        se = jnp.sum(jnp.exp(lgf - gmax[..., None]), axis=-1)
        lse = jnp.log(jax.lax.psum(se, axis)) + gmax
        lo = jax.lax.axis_index(axis) * v_local
        loc = lb - lo
        ok = (loc >= 0) & (loc < v_local)
        loc = jnp.clip(loc, 0, v_local - 1)
        picked = jnp.take_along_axis(lgf, loc[..., None], axis=-1)[..., 0]
        picked = jax.lax.psum(picked * ok.astype(jnp.float32), axis)
        total = jnp.sum(lse - picked)
        if dp:
            total = jax.lax.psum(total, dp)
        return total / n_tokens

    return jax.shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(P(dp, None, axis), P(dp, None)),
        out_specs=P(),
        axis_names=set(ctx.mesh.axis_names),
    )(logits, labels)
