"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (on CPU the
platform runs the Pallas kernel bodies in interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ann_topk import ann_topk
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ref import (
    ann_topk_ref, decode_attention_ref, flash_attention_ref,
)


@pytest.mark.parametrize(
    "n,d,b,k",
    [(1000, 128, 4, 4), (513, 64, 1, 8), (2048, 256, 16, 4), (64, 32, 2, 4)],
)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_ann_topk(n, d, b, k, dtype, rng):
    emb = rng.standard_normal((n, d)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    act = rng.random(n) > 0.2
    q = rng.standard_normal((b, d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    embj = jnp.asarray(emb).astype(dtype)
    qj = jnp.asarray(q).astype(dtype)
    v1, i1 = ann_topk(embj, jnp.asarray(act), qj, k)
    v2, i2 = ann_topk_ref(embj, jnp.asarray(act), qj, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=2e-5)
    # indices may differ only where scores tie (bf16); check score parity
    s = (embj.astype(jnp.float32) @ qj.astype(jnp.float32).T)
    for bi in range(b):
        sv1 = np.asarray(s[np.asarray(i1)[bi], bi])
        sv2 = np.asarray(s[np.asarray(i2)[bi], bi])
        np.testing.assert_allclose(sv1, sv2, atol=2e-5)


@pytest.mark.parametrize(
    "b,sq,sk,kv,g,dh,causal,win,bq,bk",
    [
        (2, 256, 256, 2, 2, 32, True, None, 64, 64),
        (1, 128, 128, 4, 1, 64, True, 48, 64, 32),
        (2, 128, 256, 2, 4, 16, False, None, 128, 128),
        (1, 512, 512, 1, 8, 128, True, None, 256, 128),
    ],
)
def test_flash_attention(b, sq, sk, kv, g, dh, causal, win, bq, bk, rng):
    q = jnp.asarray(rng.standard_normal((b, sq, kv, g, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, kv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, kv, dh)), jnp.float32)
    scale = 1 / np.sqrt(dh)
    o1 = flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                             window=win, bq=bq, bk=bk)
    o2 = flash_attention_ref(q, k, v, scale, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)


def test_flash_attention_bf16(rng):
    b, s, kv, g, dh = 1, 128, 2, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, kv, g, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.bfloat16)
    o1 = flash_attention_fwd(q, k, v, scale=0.17, bq=64, bk=64)
    o2 = flash_attention_ref(q, k, v, 0.17)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32), atol=3e-2
    )


@pytest.mark.parametrize(
    "b,kv,g,dh,s,pos,bs",
    [
        (2, 2, 4, 32, 256, 100, 64),
        (1, 4, 1, 64, 512, 511, 128),
        (4, 1, 8, 16, 128, 0, 128),
        (1, 8, 16, 128, 1024, 700, 256),
    ],
)
def test_decode_attention(b, kv, g, dh, s, pos, bs, rng):
    q = jnp.asarray(rng.standard_normal((b, kv, g, dh)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, s, kv, dh)), jnp.float32)
    scale = 1 / np.sqrt(dh)
    o1 = decode_attention(q, kc, vc, pos, scale=scale, bs=bs)
    o2 = decode_attention_ref(q, kc, vc, pos, scale)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)


@pytest.mark.parametrize("c,cap,d,b,nprobe,k", [
    (8, 16, 32, 4, 3, 2), (16, 64, 64, 8, 5, 4), (4, 8, 16, 1, 4, 3),
])
def test_ann_topk_ivf(c, cap, d, b, nprobe, k, rng):
    """Scalar-prefetch routed scan vs a per-(query, probe) numpy oracle:
    identical values; indices may differ only on fully-masked (NEG)
    slots, which callers filter via vals > NEG/2."""
    from repro.kernels.ann_topk_ivf import NEG, ann_topk_ivf

    buckets = rng.standard_normal((c, cap, d)).astype(np.float32)
    valid = (rng.random((c, cap)) > 0.3).astype(np.int32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    sel = np.stack([
        rng.choice(c, nprobe, replace=False) for _ in range(b)
    ]).astype(np.int32)
    en = (rng.random((b, nprobe)) > 0.2).astype(np.int32)
    vals, idx = ann_topk_ivf(jnp.asarray(sel), jnp.asarray(en),
                             jnp.asarray(q), jnp.asarray(buckets),
                             jnp.asarray(valid), k)
    vals, idx = np.asarray(vals), np.asarray(idx)
    for bi in range(b):
        for j in range(nprobe):
            s = buckets[sel[bi, j]] @ q[bi]
            s = np.where((valid[sel[bi, j]] > 0) & (en[bi, j] > 0), s, NEG)
            order = np.argsort(-s, kind="stable")[:k]
            np.testing.assert_allclose(vals[bi, j], s[order], atol=2e-5)
            # indices may differ where scores tie to fp ulp (the
            # ann_topk test idiom): check score parity at chosen slots
            live = s[order] > NEG / 2
            np.testing.assert_allclose(
                s[idx[bi, j][live]], s[order][live], atol=2e-5
            )


def test_ann_topk_ivf_quant(rng):
    """int8 routed coarse scan: exact int32 scores rescaled in the same
    order as the numpy path (row scale, then query scale)."""
    from repro.core.tiers import quantize_rows
    from repro.kernels.ann_topk_ivf import NEG, ann_topk_ivf_quant

    c, cap, d, b, nprobe, k = 8, 32, 48, 4, 4, 6
    emb = rng.standard_normal((c, cap, d)).astype(np.float32)
    bq, bscale = quantize_rows(emb.reshape(-1, d))
    bq = bq.reshape(c, cap, d)
    bscale = bscale.reshape(c, cap).astype(np.float32)
    valid = (rng.random((c, cap)) > 0.25).astype(np.int32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    qq, qs = quantize_rows(q)
    sel = np.stack([
        rng.choice(c, nprobe, replace=False) for _ in range(b)
    ]).astype(np.int32)
    en = np.ones((b, nprobe), np.int32)
    vals, idx = ann_topk_ivf_quant(
        jnp.asarray(sel), jnp.asarray(en), jnp.asarray(qq),
        jnp.asarray(qs), jnp.asarray(bq), jnp.asarray(bscale),
        jnp.asarray(valid), k,
    )
    vals, idx = np.asarray(vals), np.asarray(idx)
    for bi in range(b):
        for j in range(nprobe):
            s = (bq[sel[bi, j]].astype(np.int32) @ qq[bi].astype(np.int32)
                 ).astype(np.float32)
            s = s * bscale[sel[bi, j]]
            s = s * qs[bi]
            s = np.where(valid[sel[bi, j]] > 0, s, NEG)
            order = np.argsort(-s, kind="stable")[:k]
            np.testing.assert_allclose(vals[bi, j], s[order], atol=0)
            live = s[order] > NEG / 2
            assert np.array_equal(idx[bi, j][live], order[live])
