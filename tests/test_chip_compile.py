"""The stage-1 kernels compiled for a TPU v5e, without one.

The TPU compiler is installed here and compiles for a described (not
attached) ``v5e:2x2`` topology, so these tests catch what interpret mode
cannot — block shapes off the (8, 128) tiling, layouts Mosaic will not
lower, VMEM over-use — at deployment sizes, at no chip time. The
topology is described inside a module fixture (never at import: only
one process may load the TPU library, and every test worker imports
this file). The persistent compile cache is off around these compiles:
an entry compiled for a described chip cannot be read back here.

Also here: the CPU check that a served run on the kernel backend
(interpret mode) matches the numpy backend's summary.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

N, D = 1 << 20, 1024
C, CAP, NPROBE = 1280, 1024, 8   # IVF buckets: 2^20 rows at ~80% fill


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _structs(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b", [8, 64])
def test_ann_topk_compiles(one_chip, b):
    from repro.kernels.ann_topk import ann_topk

    _compile(lambda e, a, q: ann_topk(e, a, q, 4, interpret=False),
             _structs(one_chip, ((N, D), jnp.float32), ((N,), jnp.bool_),
                      ((b, D), jnp.float32)))


@pytest.mark.parametrize("b", [8, 64])
def test_ann_topk_quant_compiles(one_chip, b):
    from repro.kernels.ann_topk_quant import ann_topk_quant

    _compile(lambda e, s, a, q, qs: ann_topk_quant(e, s, a, q, qs, 16,
                                                   interpret=False),
             _structs(one_chip, ((N, D), jnp.int8), ((N,), jnp.float32),
                      ((N,), jnp.bool_), ((b, D), jnp.int8),
                      ((b,), jnp.float32)))


@pytest.mark.parametrize("b", [8, 64])
def test_ann_topk_ivf_compiles(one_chip, b):
    from repro.kernels.ann_topk_ivf import ann_topk_ivf

    _compile(lambda sel, en, q, bk, v: ann_topk_ivf(sel, en, q, bk, v, 4,
                                                    interpret=False),
             _structs(one_chip, ((b, NPROBE), jnp.int32),
                      ((b, NPROBE), jnp.int32), ((b, D), jnp.float32),
                      ((C, CAP, D), jnp.float32), ((C, CAP), jnp.int32)))


@pytest.mark.parametrize("b", [8, 64])
def test_ann_topk_ivf_quant_compiles(one_chip, b):
    from repro.kernels.ann_topk_ivf import ann_topk_ivf_quant

    _compile(lambda sel, en, q, qs, bk, sc, v: ann_topk_ivf_quant(
                 sel, en, q, qs, bk, sc, v, 16, interpret=False),
             _structs(one_chip, ((b, NPROBE), jnp.int32),
                      ((b, NPROBE), jnp.int32), ((b, D), jnp.int8),
                      ((b,), jnp.float32), ((C, CAP, D), jnp.int8),
                      ((C, CAP), jnp.float32), ((C, CAP), jnp.int32)))


@pytest.mark.parametrize("quant", [False, True])
def test_four_shard_ivf_scan_compiles(topo, quant):
    """The §13 shard_map program on a 4-device ("shards",) mesh of the
    described chips: 2^21 rows, each shard's bucket slice on its own
    chip, one Pallas scan per shard."""
    from jax.sharding import Mesh

    from repro.kernels.ann_topk_sharded import mesh_scan

    s, cmax, b = 4, 2 * C // 4 + 64, 8
    mesh = Mesh(np.array(topo.devices[:s]), ("shards",))
    shard, rep = NamedSharding(mesh, P("shards")), NamedSharding(mesh, P())
    emb_dt = jnp.int8 if quant else jnp.float32
    sharded = [((s, cmax, CAP, D), emb_dt)]
    if quant:
        sharded.append(((s, cmax, CAP), jnp.float32))
    sharded += [((s, cmax, CAP), jnp.int32), ((s, cmax, CAP), jnp.int32),
                ((s, 1), jnp.int32), ((s, 1), jnp.int32)]
    replicated = [((b, D), emb_dt)]
    if quant:
        replicated.append(((b,), jnp.float32))
    replicated += [((b, NPROBE), jnp.int32), ((b, NPROBE), jnp.int32)]
    fn = mesh_scan(mesh, 16 if quant else 4, quant, interpret=False)
    compiled = fn.lower(*_structs(shard, *sharded),
                        *_structs(rep, *replicated)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = cmax * CAP * D * s * jnp.dtype(emb_dt).itemsize
    assert per_chip < whole / 2   # the bucket stack is split, not copied


def test_served_run_kernel_backend_matches_numpy():
    """run_once on the kernel backend (Pallas in interpret mode on the
    CPU) gives the numpy backend's summary for the same seed, brute force
    and clustered with an int8 warm tier."""
    from repro.launch.serve import run_once

    for kw in (dict(n_requests=120, n_intents=300),
               dict(n_requests=600, n_intents=8000, cache_ratio=0.08,
                    cluster=True, warm_frac=0.5)):
        common = dict(workload="zipf", mode="cortex", dim=32,
                      concurrency=8, seed=3, **kw)
        assert run_once(backend="kernel", **common) == \
            run_once(backend="numpy", **common)
