"""Weights for the served qwen3-family models, and their plain reference.

The benchmark makes the judge's and the embedder's weights itself, from
the seed, in one jitted call each, in the type they are served in
(bfloat16 matrices, float32 norm scales), and hands them to the program.
The reference below imports nothing of the program: it is a plain
float32 forward written from the configuration file, with every matmul
at HIGHEST precision, run layer by layer so that it fits beside whatever
else is on the chip. With ``control=True`` every matmul operand is
rounded to float8 (e4m3) first: the precision one step below the
configuration's bfloat16, which the comparison has to reject.

What the program computes, and so what the reference computes too
(departures from Qwen3, recorded in the configuration files):
  * byte tokens (UTF-8 bytes + 3, 0 = pad) folded into the vocabulary;
  * no per-head QK-norm;
  * embedder: masked mean-pool of the last hidden state, L2-normalised;
  * judge: sigmoid(mean over d_model of the last position's hidden state);
  * neither applies the final norm.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def jax_key(seed: int):
    """A PRNG key that uses every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def byte_tokens(texts, max_len: int, vocab: int) -> np.ndarray:
    """(len(texts), max_len) int32: UTF-8 bytes + 3, 0-padded, mod vocab."""
    out = np.zeros((len(texts), max_len), np.int32)
    for i, t in enumerate(texts):
        raw = np.frombuffer(t.encode("utf-8")[:max_len], np.uint8)
        out[i, :len(raw)] = raw.astype(np.int32) + 3
    return out % vocab


def dims(cfg: dict) -> dict:
    """The model sizes of a configuration file (HF config.json keys)."""
    return dict(
        d=int(cfg["hidden_size"]), layers=int(cfg["num_hidden_layers"]),
        heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), dh=int(cfg["head_dim"]),
        ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
    )


def make_weights(cfg: dict, seed: int):
    """The served parameter tree, drawn on the default device in one
    jitted call: bfloat16 matrices with std 1/sqrt(fan_in) (embedding
    0.02), float32 norm scales near 1 (so a dropped scale shows)."""
    m = dims(cfg)
    d, n, hq, hk, ff = m["d"], m["layers"], m["heads"] * m["dh"], \
        m["kv"] * m["dh"], m["ff"]
    mats = {"wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}

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


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(a, b, control: bool):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head: x (B, S, H, Dh)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, half)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def hidden(w, tokens: np.ndarray, cfg: dict, control: bool = False):
    """Last hidden state (B, S, D) float32 of the plain forward, one
    layer at a time."""
    m = dims(cfg)
    mt = tuple(sorted(m.items()))
    table = w["embed"]["table"]
    x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    if control:
        x = _fp8(x)
    blocks = w["blocks"]["l0"]
    for li in range(m["layers"]):
        x = _layer(x, blocks, jnp.int32(li), mt=mt, control=control)
    return x


def embed_ref(w, tokens: np.ndarray, cfg: dict, control: bool = False):
    """(B, D) unit vectors: masked mean-pool of the last hidden state."""
    x = hidden(w, tokens, cfg, control)
    mask = (jnp.asarray(tokens) > 0).astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * mask, 1) / jnp.maximum(jnp.sum(mask, 1), 1.0)
    return np.asarray(pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6))


def judge_logit_ref(w, tokens: np.ndarray, cfg: dict,
                    control: bool = False) -> np.ndarray:
    """(B,) judge logits: mean over d_model of the last position."""
    x = hidden(w, tokens, cfg, control)
    return np.asarray(jnp.mean(x[:, -1, :], axis=-1), np.float64)
