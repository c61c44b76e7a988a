"""The qwen3 family: the program's dense GQA model for a Qwen3
``config.json``, its served weights and its plain reference.

What the program computes, and so what the reference computes too
(departures from Qwen3, recorded in the configuration files):
  * no per-head QK-norm;
  * no final norm (the readouts in ``model_ref`` read the last layer's
    residual stream).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from model_ref import HIGHEST, _fp8, _mm, _rms, _rope, jax_key


def dims(hf: dict) -> dict:
    """The model sizes of a configuration file (HF config.json keys)."""
    return dict(
        d=int(hf["hidden_size"]), layers=int(hf["num_hidden_layers"]),
        heads=int(hf["num_attention_heads"]),
        kv=int(hf["num_key_value_heads"]), dh=int(hf["head_dim"]),
        ff=int(hf["intermediate_size"]), vocab=int(hf["vocab_size"]),
        theta=float(hf["rope_theta"]), eps=float(hf["rms_norm_eps"]),
    )


def program_config(hf: dict, name: str):
    """The program's ModelConfig for a published config."""
    from repro.nn.config import AttnConfig, LayerSpec, ModelConfig

    m = dims(hf)
    if m["layers"] < 2:
        raise ValueError("the served models stack layers: need >= 2")
    attn = AttnConfig(n_heads=m["heads"], n_kv_heads=m["kv"],
                      head_dim=m["dh"], rope_theta=m["theta"])
    return ModelConfig(
        name=name, family="dense", d_model=m["d"],
        vocab_size=m["vocab"], n_repeat=m["layers"], norm_eps=m["eps"],
        blocks=(LayerSpec(kind="attn", attn=attn, d_ff=m["ff"]),),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype="bfloat16", compute_dtype="bfloat16")


def _matrices(m: dict) -> dict:
    d, hq, hk, ff = m["d"], m["heads"] * m["dh"], m["kv"] * m["dh"], m["ff"]
    return {"wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def make_weights(hf: dict, seed: int):
    """The served parameter tree, drawn on the default device in one
    jitted call: bfloat16 matrices with std 1/sqrt(fan_in) (embedding
    0.02), float32 norm scales near 1 (so a dropped scale shows)."""
    m = dims(hf)
    d, n = m["d"], m["layers"]
    mats = _matrices(m)

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, len(mats) + 4)

        def mat(k, shape, std):
            x = jax.random.normal(k, shape, jnp.float32) * std
            return x.astype(jnp.bfloat16)

        def scale(k, shape):
            return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

        w = {name: mat(ks[i], (n, *shape), 1.0 / math.sqrt(shape[0]))
             for i, (name, shape) in enumerate(sorted(mats.items()))}
        blk = {"norm1": {"scale": scale(ks[-4], (n, d))},
               "norm2": {"scale": scale(ks[-3], (n, d))},
               "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
               "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
        return {"embed": {"table": mat(ks[-2], (m["vocab"], d), 0.02)},
                "final_norm": {"scale": scale(ks[-1], (d,))},
                "blocks": {"l0": blk}}

    return draw(jax_key(seed))


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _layer(x, blocks, li, *, mt, control):
    """Layer ``li`` of the stacked weights, upcast to float32: a pre-norm
    decoder layer, causal GQA attention with RoPE then a SwiGLU
    feed-forward, each added to the residual stream."""
    m = dict(mt)
    w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, li, 0, keepdims=False).astype(jnp.float32), blocks)
    b, s, d = x.shape
    hq, hk, dh = m["heads"], m["kv"], m["dh"]
    h = _rms(x, w["norm1"]["scale"], m["eps"])
    q = _mm(h, w["mixer"]["wq"], control).reshape(b, s, hq, dh)
    k = _mm(h, w["mixer"]["wk"], control).reshape(b, s, hk, dh)
    v = _mm(h, w["mixer"]["wv"], control).reshape(b, s, hk, dh)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    k = jnp.repeat(k, hq // hk, axis=2)        # query head i reads kv i // g
    v = jnp.repeat(v, hq // hk, axis=2)
    if control:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    if control:
        p, v = _fp8(p), _fp8(v)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(b, s, hq * dh), w["mixer"]["wo"], control)
    h = _rms(x, w["norm2"]["scale"], m["eps"])
    g = jax.nn.silu(_mm(h, w["ffn"]["w_gate"], control))
    u = _mm(h, w["ffn"]["w_up"], control)
    return x + _mm(g * u, w["ffn"]["w_down"], control)


def hidden(w, tokens: np.ndarray, hf: dict, control: bool = False):
    """Last hidden state (B, S, D) float32 of the plain forward, one
    layer at a time."""
    m = dims(hf)
    mt = tuple(sorted(m.items()))
    table = w["embed"]["table"]
    x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    if control:
        x = _fp8(x)
    blocks = w["blocks"]["l0"]
    for li in range(m["layers"]):
        x = _layer(x, blocks, jnp.int32(li), mt=mt, control=control)
    return x


def forward_flops(hf: dict, seq: int) -> float:
    """FLOPs of one forward over ``seq`` tokens, up to the last hidden
    state (no logits): projections and feed-forward per token, causal
    attention over the pairs (i, j <= i)."""
    m = dims(hf)
    hq = m["heads"] * m["dh"]
    per_tok = 2 * sum(a * b for a, b in _matrices(m).values())
    pairs = seq * (seq + 1) // 2
    attn = 2 * 2 * pairs * hq              # q.k and p.v
    return float(m["layers"] * (seq * per_tok + attn))


def weight_bytes(hf: dict) -> float:
    """Bytes of the weights one forward reads, as served: every layer's
    bfloat16 matrices and its two float32 norm scales. Not the embedding
    table (only the gathered rows are read) nor the final norm and the
    tied logits head, which the readouts never touch."""
    m = dims(hf)
    per_layer = 2 * sum(a * b for a, b in _matrices(m).values()) \
        + 4 * 2 * m["d"]
    return float(m["layers"] * per_layer)
