"""A tiny copy of the benchmark for tests on the CPU: the harness as it
is, one configuration and two mixes at sizes a test run holds."""
from __future__ import annotations

import json
import os
import shutil

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def tiny_config(name: str, router: bool) -> dict:
    with open(os.path.join(CHIP, "configs", "search-ivf-1m.json")) as f:
        cfg = json.load(f)
    cfg.update(name=name, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=128, vocab_size=512)
    ix = {"rows": 4096, "dim": 64, "dtype": "float32"}
    if router:
        ix["router"] = {"n_clusters": 16, "nprobe": 4,
                        "refresh_every": 1 << 20, "min_train": 256,
                        "batch_size": 1024, "iters": 4, "seed": 5}
    cfg["deployment"]["index"] = ix
    # from tiny runs on the CPU: program 3e-5 / 2e-3, float8 control
    # 6e-3 / 4e-2 (embed_gap / judge_gap); stage 1 is exact on the CPU
    cfg["limits"] = {"stage1_dsim": 1e-5, "embed_gap": 5e-4,
                     "judge_gap": 1e-2}
    return cfg


def make_root(tmp: str, seconds_rate: float = 40.0) -> str:
    """A checkout-shaped directory: the chip benchmark copied as it is,
    plus tiny cells ``tiny-ivf.zipf`` and ``tiny-brute.swe``."""
    root = os.path.join(tmp, "root")
    dst = os.path.join(root, os.path.relpath(CHIP, ROOT))
    shutil.copytree(CHIP, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, router in (("tiny-ivf", True), ("tiny-brute", False)):
        with open(os.path.join(dst, "configs", name + ".json"), "w") as f:
            json.dump(tiny_config(name, router), f)
    for mix, kw in (("tiny-zipf", dict(generator="zipf", zipf_s=0.99,
                                       n_paraphrases=20, prefill_intents=500,
                                       warmup_lookups=127,
                                       world={"n_intents": 2000})),
                    ("tiny-swe", dict(generator="swe", n_paraphrases=6,
                                      files_per_task=[3, 8], tail_files=60,
                                      warmup_tasks=30,
                                      world={"n_intents": 200}))):
        kw.update(rate_per_s=seconds_rate, population_seed=1,
                  remote={"lat_lo": 0.3, "lat_hi": 0.5, "qpm": None})
        with open(os.path.join(dst, "traffic", mix + ".json"), "w") as f:
            json.dump(kw, f)
    rel = os.path.relpath(CHIP, ROOT)
    bench["configs"] = [
        {"name": n, "source": "tiny", "file": f"{rel}/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in ("tiny-ivf", "tiny-brute")]
    cells = ["tiny-ivf.zipf", "tiny-brute.swe"]
    bench["workloads"] = [
        {"name": "tiny-ivf.zipf", "config": "tiny-ivf",
         "traffic": "tiny-zipf", "chips": 1, "why": "tiny"},
        {"name": "tiny-brute.swe", "config": "tiny-brute",
         "traffic": "tiny-swe", "chips": 1, "why": "tiny"}]
    for m in bench["per_layer"]:
        m["workloads"] = cells
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
