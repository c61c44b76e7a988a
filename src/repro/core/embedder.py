"""Embedding front-end for Seri stage 1.

Two implementations behind one interface:

* ``ModelEmbedder`` — a real JAX encoder (qwen3-0.6b at its published
  widths, or a shrink of it):
  byte-level tokens → transformer → masked mean-pool → L2-normalise. With
  random init it still yields a deterministic, locality-free fingerprint;
  it exists to measure the true compute cost of the embedding stage and to
  exercise the co-location path. (No pretrained weights exist offline.)
* ``WorldEmbedder`` — the synthetic-semantic-world embedder used for the
  paper's behavioural experiments: paraphrases of one intent share a
  cluster center, hard negatives sit at a controlled cosine distance —
  giving ANN realistic true/false-positive structure (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp


def l2_normalize(x: np.ndarray, axis: int = -1) -> np.ndarray:
    n = np.linalg.norm(x, axis=axis, keepdims=True)
    return x / np.maximum(n, 1e-9)


def byte_tokens(text: str, max_len: int) -> np.ndarray:
    raw = np.frombuffer(text.encode("utf-8")[:max_len], dtype=np.uint8)
    out = np.zeros(max_len, np.int32)
    out[: len(raw)] = raw.astype(np.int32) + 3  # 0 = pad
    return out


class ModelEmbedder:
    def __init__(self, cfg=None, dim: int = 256, max_len: int = 64, seed=0):
        from repro.configs import get_config, shrink
        from repro.models.lm import LM
        from repro.nn.param import init_tree
        from repro.nn.sharding import ShardCtx

        cfg = cfg or shrink(get_config("qwen3-0.6b"), d_model=dim, vocab=512,
                            n_repeat=2)
        self.cfg = cfg
        self.max_len = max_len
        self.lm = LM(cfg)
        self.ctx = ShardCtx(None)
        self.params = init_tree(jax.random.PRNGKey(seed), self.lm.param_specs())

        def encode(params, tokens):
            x = self.lm._embed(self.ctx, params, tokens)
            pos = self.lm._positions(tokens)
            x, _, _ = self.lm._run_stack(self.ctx, params, x, pos)
            mask = (tokens > 0).astype(jnp.float32)[..., None]
            pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(
                jnp.sum(mask, axis=1), 1.0
            )
            return pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6
            )

        self._encode = jax.jit(encode)

    @property
    def dim(self) -> int:
        return self.cfg.d_model

    def tokens(self, texts: Sequence[str]) -> np.ndarray:
        """(len(texts), max_len) int32 byte tokens, folded into vocab."""
        return np.stack([byte_tokens(t, self.max_len) for t in texts]) \
            % self.cfg.vocab_size

    def embed_batch(self, texts: Sequence[str]) -> np.ndarray:
        return np.asarray(
            self._encode(self.params, jnp.asarray(self.tokens(texts))),
            np.float32)


class WorldEmbedder:
    """Looks up embeddings from a synthetic semantic world (data.world)."""

    def __init__(self, world):
        self.world = world

    @property
    def dim(self) -> int:
        return self.world.dim

    def embed_batch(self, texts: Sequence[str]) -> np.ndarray:
        return np.stack([self.world.embed(t) for t in texts])
