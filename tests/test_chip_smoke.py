"""chip_smoke.py rehearsed on the CPU: every phase function at a tiny
size in interpret mode, the --four-chips path on 4 virtual CPU devices,
and main() refusing to run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.configs import get_config, shrink

    return shrink(get_config("qwen3-0.6b"), d_model=64, vocab=512,
                  n_repeat=2)


def test_precision_phase(smoke):
    out = smoke.phase_precision(d=128, n=128, b=8)
    assert out["pallas_highest"] < 1e-5 and out["xla_highest"] < 1e-5


def test_kernels_phase(smoke, capsys):
    smoke.phase_kernels(n=4096, d=64, batches=(8,), n_clusters=16,
                        nprobe=4)
    out = capsys.readouterr().out
    for name in ("ann_topk ", "ann_topk_ivf ", "ann_topk_quant ",
                 "ann_topk_ivf_quant "):
        assert f"  {name}" in out


def test_served_and_embedder_phases(smoke, tiny_cfg, capsys):
    from repro.core.judge import ModelJudge

    judge = ModelJudge(cfg=tiny_cfg, max_len=32, seed=6)
    runs = (("brute", dict(n_requests=60, n_intents=200)),
            ("cluster+warm", dict(n_requests=600, n_intents=8000,
                                  cache_ratio=0.08, cluster=True,
                                  warm_frac=0.5)))
    texts = smoke.phase_served(judge, runs=runs, dim=64, concurrency=4,
                               backend="kernel")
    out = capsys.readouterr().out
    assert "judge compilations" in out
    assert judge.shapes_compiled <= {1, 2, 4, 8, 16, 32}
    assert len(texts) == 60
    smoke.phase_embedder(tiny_cfg, texts[:8], n_ref=8, max_len=16)


def test_main_refuses_cpu(capsys):
    """No TPU: nonzero exit, and no result line."""
    with pytest.raises(SystemExit) as e:
        _load().main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {path!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.phase_four_chips(n=4096, d=64, batches=(8,), n_clusters=32, nprobe=4)
print("FOUR_CHIPS_PASS")
"""


def test_four_chips_phase_on_virtual_devices():
    """The mesh path (shard_map over 4 devices) against its references;
    a subprocess, since the device count is fixed at backend start."""
    code = FOUR.format(root=str(ROOT / "src"),
                       path=str(ROOT / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "FOUR_CHIPS_PASS" in r.stdout
    assert "ivf_sharded vs numpy sharded_topk_merge" in r.stdout


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
    it the cache goes to one fixed, git-ignored path in the checkout."""
    import jax

    from repro.launch import compile_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_four_chips_option_runs_only_its_phase(monkeypatch, capsys):
    """--four-chips runs the mesh phase and nothing else, and the last
    line printed is the one JSON result with the device's count."""
    smoke = _load()
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    ran = []

    def other(*a, **k):
        raise AssertionError("a one-chip phase ran under --four-chips")

    monkeypatch.setattr(smoke, "phase_device", lambda count=1: dev)
    monkeypatch.setattr(smoke, "phase_four_chips",
                        lambda seed=0: ran.append(seed))
    for name in ("phase_precision", "phase_kernels", "phase_served",
                 "phase_embedder"):
        monkeypatch.setattr(smoke, name, other)
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "cache")
    assert smoke.main(["--four-chips", "--seed", "3"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
    assert ran == [3]
