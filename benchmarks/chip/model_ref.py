"""The served models: each found by its configuration's own
``model_type``, and the plain reference's parts that every family shares.

A configuration file may hold a ``judge_model`` and an ``embedder_model``
object, each a published ``config.json``; one that has neither serves
its own top-level config as both. Each config's ``model_type`` names its
family file, ``models/<model_type>.py``, found by name as the traffic
generators and the per-layer readers are. A family file provides

    program_config(hf, name)   the program's ModelConfig
    make_weights(hf, seed)     the served parameter tree, drawn on the
                               device in one jitted call
    hidden(w, tokens, hf, control=False)
                               the plain float32 reference's last hidden
                               state, layer by layer at HIGHEST precision
    forward_flops(hf, seq)     FLOPs one forward over seq tokens needs
    weight_bytes(hf)           bytes of the weights one forward reads

so a new architecture is a new file there, never an edit.

The benchmark makes the judge's and the embedder's weights itself, from
the seed, in the type they are served in, and hands them to the program.
The reference imports nothing of the program: it is a plain float32
forward written from the published config, with every matmul at HIGHEST
precision, run layer by layer so that it fits beside whatever else is on
the chip. With ``control=True`` every matmul operand is rounded to
float8 (e4m3) first: the precision one step below the configuration's
bfloat16, which the comparison has to reject.

What the program computes around any family's forward, and so what the
reference computes too (departures recorded in the configuration files):
  * byte tokens (UTF-8 bytes + 3, 0 = pad) folded into the vocabulary;
  * embedder: masked mean-pool of the last hidden state, L2-normalised;
  * judge: sigmoid(mean over d_model of the last position's hidden state);
  * neither applies the final norm.
"""
from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType
from typing import NamedTuple

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


class Model(NamedTuple):
    """One served model: its published config and its family's file."""
    hf: dict
    family: ModuleType


_FAMILY = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def family(model_type, where: str) -> ModuleType:
    """The family file ``<where>/<model_type>.py``; an unknown
    ``model_type`` ends the run."""
    path = os.path.join(where, f"{model_type}.py")
    if not isinstance(model_type, str) or not _FAMILY.match(model_type) \
            or not os.path.isfile(path):
        raise SystemExit(f"no model family for model_type {model_type!r}: "
                         f"the benchmark has no {path}")
    spec = importlib.util.spec_from_file_location(
        "model_family_" + model_type, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def served_models(cfg: dict, where: str) -> dict:
    """``{"judge": Model, "embedder": Model}`` of a configuration file:
    its ``judge_model`` and ``embedder_model`` where it has them, its
    own top-level config otherwise. Each family file is loaded once."""
    loaded: dict = {}
    out = {}
    for role in ("judge", "embedder"):
        hf = cfg.get(role + "_model", cfg)
        mt = hf.get("model_type")
        if mt not in loaded:
            loaded[mt] = family(mt, where)
        out[role] = Model(hf, loaded[mt])
    return out


def embed_ref(model: Model, w, tokens: np.ndarray, control: bool = False):
    """(B, D) unit vectors: masked mean-pool of the last hidden state."""
    x = model.family.hidden(w, tokens, model.hf, control)
    mask = (jnp.asarray(tokens) > 0).astype(jnp.float32)[..., None]
    pooled = jnp.sum(x * mask, 1) / jnp.maximum(jnp.sum(mask, 1), 1.0)
    return np.asarray(pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6))


def judge_logit_ref(model: Model, w, tokens: np.ndarray,
                    control: bool = False) -> np.ndarray:
    """(B,) judge logits: mean over d_model of the last position."""
    x = model.family.hidden(w, tokens, model.hf, control)
    return np.asarray(jnp.mean(x[:, -1, :], axis=-1), np.float64)
