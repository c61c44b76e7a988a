"""Whole runs on the CPU at tiny sizes: discovery of cells by name, the
chip requirement, the control and the planted faults. Each run skips only
the harness's look for a chip and drives the rest: set-up, window,
reference and verdict."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import run
import tiny

CELLS = ["tiny-ivf.zipf", "tiny-brute.swe"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def go(root, cell, **kw):
    return run.run(cell, 2**31 + 1234, 2.0, False, root=root, chips=False,
                   t_start=time.time(), **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_run_is_correct(root, cell):
    out = go(root, cell)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"lookup_p50_ms", "lookup_p95_ms",
                                   "lookups_per_s", "fetches_per_lookup",
                                   "setup_s"}
    assert list(out)[-1] == "compared"
    assert out["compared"]["compiles"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(root, cell):
    out = go(root, cell, control=True)
    assert not out["correct"] and out["program"]["correct"]
    bad = {k for k, v in out["compared"].items() if v["value"] > v["limit"]}
    # each model number fails on its own; stage 1's control (HIGH) is
    # the same as HIGHEST on the CPU, so only the chip can read it
    assert {"embed_gap", "judge_gap"} <= bad
    assert bad <= {"embed_gap", "judge_gap", "stage1_dsim"}


def _plant(monkeypatch, fault):
    from repro.core import embedder, judge, seri

    if fault == "state unchanged":       # an admission stores nothing
        def add(self, se_id, embedding):
            row = self._alloc(se_id)
            if self.router is not None:
                self.router.note_add(row, self.emb[row], self)
            return row
        monkeypatch.setattr(seri.VectorIndex, "add", add)
    elif fault == "half the batch":      # stage 1 drops the larger half
        orig = seri.VectorIndex.search_batch

        def half(self, q, k, tau):
            out = orig(self, q, k, tau)[:len(q) // 2]
            return out + [([], np.zeros(0, np.float32))] * (len(q) - len(out))
        monkeypatch.setattr(seri.VectorIndex, "search_batch", half)
    elif fault == "judge token":         # judge output altered at the source
        orig = judge.ModelJudge.score_pairs
        monkeypatch.setattr(judge.ModelJudge, "score_pairs",
                            lambda self, q, c: orig(self, q, c) * 0.9)
    elif fault == "embedding":           # embedder output altered
        orig = embedder.ModelEmbedder.embed_batch

        def bent(self, texts):
            e = orig(self, texts).copy()
            e[:, 0] += 0.05
            return e
        monkeypatch.setattr(embedder.ModelEmbedder, "embed_batch", bent)
    elif fault == "served answer":       # a hit serves the wrong entry
        from repro.core import cache

        orig = cache.CortexCache.finalize

        def wrong(self, query, cands, scores, now, sims=None):
            r = orig(self, query, cands, scores, now, sims=sims)
            if r.hit:
                others = [c for c in self.store.values()
                          if c.key != r.se.key]
                r.se = others[0]
            return r
        monkeypatch.setattr(cache.CortexCache, "finalize", wrong)


@pytest.mark.parametrize("fault", ["state unchanged", "half the batch",
                                   "judge token", "embedding",
                                   "served answer"])
def test_a_planted_fault_is_caught(root, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out = go(root, "tiny-ivf.zipf")
    assert not out["correct"], (fault, out["compared"])


def test_answers_are_judged_as_served(root, monkeypatch):
    """An entry that a hit served may expire, be evicted and have its row
    taken by another entry before the check runs: the check judges each
    answer as it was served, not the row's later tenant."""
    import served

    orig = served.Served.window

    def reused(self, seconds):
        win = orig(self, seconds)
        rows = np.flatnonzero(self.cache.soa.active)
        self.cache.soa.key[rows] = "reused"
        self.cache.soa.value[rows] = "reused"
        return win
    monkeypatch.setattr(served.Served, "window", reused)
    out = go(root, "tiny-brute.swe")
    assert out["correct"], out["compared"]


def _harness_files(chip) -> dict:
    """The bytes of every file of the harness that a new cell reuses."""
    paths = ["run.py", "served.py", "check.py", "tracing.py", "counts.py",
             "model_ref.py", "traffic/gen/mix.py", "traffic/gen/swe.py",
             "traffic/gen/zipf.py"]
    for d in ("models", "layer_metrics"):
        paths += sorted(f"{d}/{p}" for p in os.listdir(os.path.join(chip, d))
                        if p.endswith(".py"))
    assert "models/qwen3.py" in paths
    return {p: open(os.path.join(chip, p), "rb").read() for p in paths}


def _add_cell(new, cfg, traffic, name, per_layer=()):
    """Configuration file ``cfg`` and cell ``name`` on mix ``traffic``
    (and per-layer metrics) as new entries of ``new``'s benchmark."""
    chip = os.path.join(new, "benchmarks", "chip")
    with open(os.path.join(chip, "configs", cfg["name"] + ".json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(new, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": cfg["name"], "source": "tiny",
        "file": f"benchmarks/chip/configs/{cfg['name']}.json",
        "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": name, "config": cfg["name"],
                               "traffic": traffic, "chips": 1,
                               "why": "tiny"})
    bench["per_layer"].extend(per_layer)
    with open(os.path.join(new, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_a_new_cell_is_found_by_name(root, tmp_path):
    """A configuration, a mix with a generator of its own and a
    per-layer metric added as new files and new entries, with no file of
    the benchmark edited."""
    new = str(tmp_path / "root")
    shutil.copytree(root, new, symlinks=True)
    chip = os.path.join(new, "benchmarks", "chip")
    before = _harness_files(chip)
    cfg = tiny.tiny_config("tiny-new", router=False)
    cfg["deployment"]["index"]["rows"] = 2048
    with open(os.path.join(chip, "traffic", "tiny-swe.json")) as f:
        mix = json.load(f)
    mix.update(rate_per_s=40.0, generator="every_other")
    with open(os.path.join(chip, "traffic", "tiny-slow.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(chip, "traffic", "gen", "every_other.py"),
              "w") as f:
        f.write("from traffic.gen.mix import generator\n\n\n"
                "def generate(spec, world, seed, seconds):\n"
                "    pre, warm, win = generator('swe')(spec, world, seed,"
                " seconds)\n"
                "    return pre, warm, win[::2]\n")
    with open(os.path.join(chip, "layer_metrics", "batches_per_s.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return len(run['passes']) / 2.0\n")
    _add_cell(new, cfg, "tiny-slow", "tiny-new.slow", per_layer=[{
        "name": "batches_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "cache", "moves": "lookups_per_s",
        "workloads": ["tiny-new.slow"]}])
    c = run.load_cell("tiny-new.slow", new)
    assert c["cfg"]["deployment"]["index"]["rows"] == 2048
    assert c["traffic"]["rate_per_s"] == 40.0
    assert c["generate"].__module__ == "traffic_generator_every_other"
    assert list(c["per_layer"]) == ["batches_per_s"]
    assert c["per_layer"]["batches_per_s"][1]({"passes": [1, 2, 3]}) == 1.5
    out = run.run("tiny-new.slow", 7, 2.0, False, root=new, chips=False,
                  t_start=time.time())
    assert out["correct"] and 20 <= out["attempted"] <= 60
    for p, data in before.items():
        assert open(os.path.join(chip, p), "rb").read() == data


def _tiny_hf(cfg, **sizes) -> dict:
    """A published-config-shaped copy of a tiny configuration's model."""
    hf = {k: v for k, v in cfg.items()
          if k not in ("name", "deployment", "limits", "departures",
                       "assumed")}
    hf.update(sizes)
    return hf


def test_a_new_architecture_is_a_new_file(root, tmp_path):
    """A model family added as a new file under ``models/``, named by
    the ``model_type`` of a judge and an embedder of different widths in
    a new configuration: the run builds each from its own config, comes
    out correct, and no file of the benchmark is edited."""
    new = str(tmp_path / "root")
    shutil.copytree(root, new, symlinks=True)
    chip = os.path.join(new, "benchmarks", "chip")
    before = _harness_files(chip)
    with open(os.path.join(chip, "models", "qwen3.py")) as f:
        family = f.read()
    with open(os.path.join(chip, "models", "qwen3_twin.py"), "w") as f:
        f.write(family)
    cfg = tiny.tiny_config("tiny-two", router=False)
    cfg["judge_model"] = _tiny_hf(cfg, model_type="qwen3_twin",
                                  hidden_size=96, intermediate_size=160)
    cfg["embedder_model"] = _tiny_hf(cfg, hidden_size=48, head_dim=8)
    _add_cell(new, cfg, "tiny-swe", "tiny-two.swe")
    c = run.load_cell("tiny-two.swe", new)
    jm, em = c["models"]["judge"], c["models"]["embedder"]
    assert jm.family.__name__ == "model_family_qwen3_twin"
    assert em.family.__name__ == "model_family_qwen3"
    assert jm.family.program_config(jm.hf, "j").d_model == 96
    assert em.family.program_config(em.hf, "e").d_model == 48
    out = run.run("tiny-two.swe", 11, 2.0, False, root=new, chips=False,
                  t_start=time.time())
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    for p, data in before.items():
        assert open(os.path.join(chip, p), "rb").read() == data


def test_an_unknown_model_type_ends_the_run(root, tmp_path):
    new = str(tmp_path / "root")
    shutil.copytree(root, new, symlinks=True)
    cfg = tiny.tiny_config("tiny-odd", router=False)
    cfg["judge_model"] = _tiny_hf(cfg, model_type="no_such_family")
    _add_cell(new, cfg, "tiny-swe", "tiny-odd.swe")
    with pytest.raises(SystemExit, match="no_such_family"):
        run.run("tiny-odd.swe", 11, 2.0, False, root=new, chips=False,
                t_start=time.time())


def _cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "code-brute-256k.swe", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _cmd(tiny.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "runs on a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
