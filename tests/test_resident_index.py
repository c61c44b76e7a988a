"""The kernel backend's device mirror of the brute stage-1 rows
(DESIGN.md §8, "Resident index"): synced by dirty rows, one compiled
scatter shape, a whole put only where scattering is not worth it; and
the brute pass around it: queries padded on the host, one put, one
dispatch, one fetch. Pallas kernels run interpreted on the CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import seri
from repro.core.cache import make_cache
from repro.core.judge import OracleJudge
from repro.core.seri import SYNC_CHUNK, VectorIndex
from repro.core.tiers import QuantIndex, make_tiered_cache
from repro.data.world import SemanticWorld
from repro.kernels.ann_topk import ann_topk
from repro.kernels.ops import ann_topk_jit

DIM, CAP = 32, 256
# a gate every real row clears and the numpy scan's -1.0 fill for
# inactive rows does not
TAU = -0.99
FLAVOURS = {"fp32": VectorIndex, "int8": QuantIndex}


class _Compiles:
    """Compilations (tracing, lowering, backend compiles) while ``on``,
    counted as the chip benchmark counts them inside its window."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, *_a, **_k):
        if self.on and name.startswith("/jax/core/compile/"):
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def _unit(rng, n):
    x = rng.standard_normal((n, DIM)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _pair(flavour):
    cls = FLAVOURS[flavour]
    return cls(CAP, DIM, backend="kernel"), cls(CAP, DIM, backend="numpy")


def _mirror_matches(ix, sync=False):
    """The device mirror equals the host arrays: as the last pass left
    it, or (``sync``) once the rows changed since are synced."""
    if sync:
        ix._sync_mirror()
    assert ix._mirror is not None and not ix._dirty
    for dev, host in zip(ix._mirror, ix._mirrored()):
        np.testing.assert_array_equal(np.asarray(dev), host)


def _query_bytes(flavour, q):
    """What a pass puts of its queries: fp32 rows padded on the host to
    the kernel's 8-row block, or int8 rows and their fp32 scales."""
    b = q.shape[0]
    return -(-b // 8) * 8 * DIM * 4 if flavour == "fp32" else b * (DIM + 4)


def _same_results(flavour, kr, npy, q):
    got, want = kr.search_batch(q, 4, TAU), npy.search_batch(q, 4, TAU)
    for (ids_k, sims_k), (ids_n, sims_n) in zip(got, want):
        assert ids_k == ids_n
        if flavour == "int8":     # same integers, same host rescore
            np.testing.assert_array_equal(sims_k, sims_n)
        else:
            np.testing.assert_allclose(sims_k, sims_n, atol=2e-5)
    if flavour == "fp32" and len(kr):
        # the resident mirror feeds the kernel exactly the fp32 values a
        # whole put of the host arrays would: bit-identical scores
        sims, rows = map(np.asarray, ann_topk_jit(kr.emb, kr.active, q, 4))
        for i, (ids_k, sims_k) in enumerate(got):
            keep = sims[i] >= TAU
            assert ids_k == kr.row_se[rows[i][keep]].tolist()
            np.testing.assert_array_equal(sims_k, sims[i][keep])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_mirror_follows_random_mutations(flavour, seed):
    rng = np.random.default_rng(seed)
    kr, npy = _pair(flavour)
    ids = iter(range(10 ** 6))
    q = _unit(rng, 3)
    for step in range(12):
        op = rng.integers(3) if step else 1
        live = np.flatnonzero(kr.active)
        if op == 0:
            for _ in range(min(int(rng.integers(1, 5)), len(kr._free))):
                e, i = _unit(rng, 1)[0], next(ids)
                assert kr.add(i, e) == npy.add(i, e)
        elif op == 1:
            n = min(int(rng.integers(1, 90)), len(kr._free))
            e, i = _unit(rng, n), [next(ids) for _ in range(n)]
            np.testing.assert_array_equal(kr.add_batch(i, e),
                                          npy.add_batch(i, e))
        elif len(live):
            drop = rng.choice(live, min(len(live),
                                        int(rng.integers(1, 40))), False)
            kr.remove_rows(drop.tolist())
            npy.remove_rows(drop.tolist())
        q = _unit(rng, 3)
        _same_results(flavour, kr, npy, q)
        _mirror_matches(kr, sync=not len(kr))   # an empty index runs no pass
    assert npy._mirror is None and not npy._dirty


@pytest.mark.parametrize("n_dirty", [1, SYNC_CHUNK, SYNC_CHUNK + 1])
@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_the_scatter_compiles_once(flavour, n_dirty, compiles):
    seri._sync_rows.clear_cache()      # count this test's compiles alone
    rng = np.random.default_rng(n_dirty)
    kr, npy = _pair(flavour)
    e = _unit(rng, 200)
    kr.add_batch(np.arange(200), e)
    npy.add_batch(np.arange(200), e)
    q = _unit(rng, 3)
    kr.search_batch(q, 4, TAU)         # builds the mirror, warms the shape
    assert kr.last_full_uploads == 1 and seri._sync_rows._cache_size() == 1
    kr.remove_rows(range(n_dirty))
    npy.remove_rows(range(n_dirty))
    compiles.on, n0 = True, compiles.n
    try:
        _same_results(flavour, kr, npy, q)
    finally:
        compiles.on = False
    assert compiles.n == n0
    assert seri._sync_rows._cache_size() == 1
    assert kr.last_full_uploads == 0 and kr.last_synced_rows == n_dirty
    _mirror_matches(kr)


@pytest.mark.parametrize("over", [False, True])
@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
def test_a_dirty_set_over_the_threshold_puts_the_whole_arrays(flavour,
                                                               over):
    rng = np.random.default_rng(7)
    kr, npy = _pair(flavour)
    kr.add_batch(np.arange(CAP), _unit(rng, CAP))
    q = _unit(rng, 2)
    kr.search_batch(q, 4, TAU)
    whole = sum(a.nbytes for a in kr._mirrored())
    row = whole // CAP + 4               # a row's bytes and its int32 index
    chunks = -(-whole // (row * SYNC_CHUNK))   # first chunk count not worth it
    n = (chunks - 1) * SYNC_CHUNK + (1 if over else 0)
    kr.remove_rows(range(n))
    kr.search_batch(q, 4, TAU)
    if over:
        assert (kr.last_full_uploads, kr.last_synced_rows) == (1, 0)
        assert kr.last_h2d_bytes == whole + _query_bytes(flavour, q)
    else:
        assert (kr.last_full_uploads, kr.last_synced_rows) == (0, n)
        assert kr.last_h2d_bytes == n // SYNC_CHUNK * SYNC_CHUNK * row \
            + _query_bytes(flavour, q)
    _mirror_matches(kr)


def _device_padded_pass(ix, q, k):
    """The brute pass as the device used to pad it: the queries put as
    they are, padded and sliced by eager device ops, the rows and the
    scores fetched one after the other."""
    b = q.shape[0]
    emb, active = ix._mirror
    qd = jnp.pad(jax.device_put(q), ((0, (-b) % 8), (0, 0)))
    vals, rows = ann_topk(emb, active, qd, k)
    return np.asarray(rows[:b]), np.asarray(vals[:b])


@pytest.mark.parametrize("b", [1, 3, 8, 9, 64])
def test_the_host_padded_pass_matches_both_references(b):
    """Exactly the numpy backend's rows, and bit for bit the scores of
    the same kernel fed a block padded on the device."""
    rng = np.random.default_rng(b)
    kr, npy = _pair("fp32")
    e = _unit(rng, 200)
    kr.add_batch(np.arange(200), e)
    npy.add_batch(np.arange(200), e)
    q = _unit(rng, b)
    rows, sims = kr._search_brute(q, 4)
    assert rows.shape == sims.shape == (b, 4)
    np.testing.assert_array_equal(rows, npy.numpy_brute(q, 4)[0])
    want_rows, want_sims = _device_padded_pass(kr, q, 4)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(sims, want_sims)


def test_a_brute_pass_blocks_on_the_device_once():
    rng = np.random.default_rng(5)
    kr, _ = _pair("fp32")
    kr.add_batch(np.arange(100), _unit(rng, 100))
    for b in (1, 2, 5, 8, 13):
        kr.search_batch(_unit(rng, b), 4, TAU)
        assert kr.last_d2h_waits == 1
        # int32 rows and fp32 scores of the padded block
        assert kr.last_d2h_bytes == -(-b // 8) * 8 * 4 * (4 + 4)


def test_small_batches_share_the_8_row_program(compiles):
    """After one pass at b = 8, passes at b = 1, 2 and 4 compile
    nothing: the host pads them to the same block, and no pad or slice
    runs on the device."""
    rng = np.random.default_rng(6)
    kr, npy = _pair("fp32")
    e = _unit(rng, 150)
    kr.add_batch(np.arange(150), e)
    npy.add_batch(np.arange(150), e)
    kr.search_batch(_unit(rng, 8), 4, TAU)
    compiles.on, n0 = True, compiles.n
    try:
        for b in (1, 2, 4):
            q = _unit(rng, b)
            got = kr.search_batch(q, 4, TAU)
            assert [ids for ids, _ in got] == [
                ids for ids, _ in npy.search_batch(q, 4, TAU)]
    finally:
        compiles.on = False
    assert compiles.n == n0


class _Decide:
    def score_pairs(self, queries, keys):
        return np.full(len(queries), 0.95, np.float32)

    def staticity(self, query):
        return 5


def test_scan_metrics_count_uploads_and_synced_rows():
    rng = np.random.default_rng(4)
    cache = make_cache(capacity_bytes=1 << 40, dim=DIM, index_capacity=CAP,
                       judge=_Decide(), backend="kernel")
    embs = _unit(rng, CAP)
    kw = dict(cost=0.01, latency=0.1, size=10, staticity=5, ttl=1e9)
    cache.insert_block([f"k{i}" for i in range(CAP)], embs, ["v"] * CAP,
                       now=0.0, **kw)
    sc = cache.scan
    cache.lookup_batch(["a"], embs[:1], now=1.0)
    assert (sc.full_uploads, sc.synced_rows) == (1, 0)
    cache.lookup_batch(["b"], embs[1:2], now=2.0)       # clean: queries only
    assert (sc.full_uploads, sc.synced_rows) == (1, 0)
    cache.insert("new", _unit(rng, 1)[0], "v", now=3.0, **kw)  # evicts one
    cache.lookup_batch(["c"], embs[2:3], now=4.0)
    assert (sc.full_uploads, sc.synced_rows) == (1, 1)
    # three one-query passes, each padded on the host to 8 fp32 rows
    assert sc.h2d_bytes == (CAP * (DIM * 4 + 1) + 3 * 8 * DIM * 4
                            + SYNC_CHUNK * (DIM * 4 + 1 + 4))
    _mirror_matches(cache.seri.index)


def _tiered(backend, world):
    return make_tiered_cache(
        hot_bytes=500, warm_bytes=10_000, dim=world.dim,
        judge=OracleJudge(world, accuracy=1.0, seed=1), index_capacity=CAP,
        max_ttl=800.0, backend=backend)


def test_a_demote_promote_cycle_keeps_the_warm_mirror():
    world = SemanticWorld(n_intents=60, dim=48, seed=7)
    caches = {b: _tiered(b, world) for b in ("kernel", "numpy")}
    out = {b: [] for b in caches}
    for b, cache in caches.items():
        now = 0.0
        for rnd in range(3):
            for i in range(12 * rnd, 12 * rnd + 12):   # hot holds 5
                q = world.query(i, 0)
                cache.insert(q, world.embed(q), world.fetch(q), now=now,
                             size=100, cost=0.01, latency=0.4)
                now += 1.0
            for i in range(12 * rnd, 12 * rnd + 6):    # demoted: promote
                q = world.query(i, 5)
                r = cache.lookup(q, world.embed(q), now)
                out[b].append((r.hit, r.se.se_id if r.hit else None))
                now += 1.0
    assert out["kernel"] == out["numpy"]
    kc = caches["kernel"]
    ts = kc.tier_stats
    assert ts.demotions and ts.promotions and kc.scan.synced_rows
    _mirror_matches(kc.warm.index, sync=True)
    _mirror_matches(kc.seri.index, sync=True)
