"""The model families under ``models/``: found by ``model_type``, and the
qwen3 family drawing the same weights and computing the same reference
as before it had a file of its own."""
import hashlib
import json
import os

import numpy as np
import pytest

import jax

import model_ref
import tiny

MODELS = os.path.join(tiny.CHIP, "models")

# sha256 of make_weights and hidden on the tiny configuration, computed
# with the single-family model_ref.py that the qwen3 family file replaced
PINNED = {
    7: ("39066b1d941f6a46e25e68da42d563ddafbc64ab340c0015908a27d1d8a07df7",
        "df6021ee2683b88c5359ab60ca9ff527275eb79aa0d1f859914ce0db50b93e1f",
        "3cdc4f87aa97eb659be1907154c4710c384ed941de3f9b13180e084724699650"),
    2**31 + 1234: (
        "a9cf1f0843577fe98239f1ed8c4391defac089e879048cffe3bfe1ecd99c8f2b",
        "b8384176e5f04b46e1805853be07fd7f3a016b47ff7e574c95f644614ded2cd9",
        "8fd5036dece45d59ba39a76c7edeeb2f2662e6c16dbbf02ac6feb01006478e35"),
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_qwen3_weights_and_reference_are_unchanged(seed):
    cfg = tiny.tiny_config("tiny-brute", router=False)
    qwen3 = model_ref.family("qwen3", MODELS)
    toks = model_ref.byte_tokens(["read src/cache.py lines 1-80",
                                  "grep -n judge src/", "x"], 24,
                                 int(cfg["vocab_size"]))
    w = qwen3.make_weights(cfg, seed)
    got = (digest(w), digest(qwen3.hidden(w, toks, cfg)),
           digest(qwen3.hidden(w, toks, cfg, control=True)))
    assert got == PINNED[seed]


def test_a_file_without_models_serves_itself_as_both():
    with open(os.path.join(tiny.CHIP, "configs", "code-brute-256k.json")) as f:
        cfg = json.load(f)
    m = model_ref.served_models(cfg, MODELS)
    assert m["judge"].hf is cfg and m["embedder"].hf is cfg
    assert m["judge"].family is m["embedder"].family      # loaded once
    pc = m["judge"].family.program_config(cfg, cfg["name"])
    assert (pc.d_model, pc.n_repeat, pc.vocab_size) == (1024, 28, 151936)
