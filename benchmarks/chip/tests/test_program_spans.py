"""The program's own wall spans (``Tracer.wall``, bound with
``bind_tracer``) beside the benchmark: they never reach the benchmark's
span reading, a run with a tracer bound stays correct, and
``program_spans.py`` reads them."""

import jax
import jax.numpy as jnp
import pytest

import program_spans
import tiny
import tracing


def test_program_spans_do_not_reach_the_benchmark_spans(tmp_path):
    """A real CPU trace holding the benchmark's spans and, inside them,
    program wall spans: the host spans read are the benchmark's alone, as
    without the program's, so every ``summarize`` key reads as before;
    ``idle_by_program_span`` reads the program's from the same file."""
    from repro.obs.trace import Tracer

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("lookup"):
            with tr.wall("cache.lookup_batch"), tr.wall("seri.search"):
                with tr.wall("seri.h2d"):
                    y = jax.device_put(x)
                with tr.wall("seri.scan"):
                    f(y).block_until_ready()
    jax.profiler.stop_trace()
    pd = tracing.load(str(tmp_path))
    _, host = tracing.read_events(pd)
    assert sorted(h[2] for h in host) == ["lookup", "window"]
    names = {s.name for s in tr.wall_spans}
    assert names.isdisjoint(tracing.SPANS)
    # no TPU plane on the CPU: the whole window is idle, and each program
    # span's own time takes its share of it
    got = program_spans.idle_by_program_span(pd, names)
    assert set(got["idle_self_s"]) == names
    assert got["idle_s"] == pytest.approx(got["window_s"])
    assert sum(got["idle_self_s"].values()) + got["idle_outside_s"] \
        == pytest.approx(got["idle_s"])
    h2d = [s for s in tr.wall_spans if s.name == "seri.h2d"][0]
    assert got["idle_self_s"]["seri.h2d"] == pytest.approx(
        h2d.t1 - h2d.t0, rel=0.05, abs=1e-4)


def test_idle_is_charged_to_the_innermost_span():
    spans = [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d"),
             (12, 14, "e")]
    idle = [[1, 3.5], [7, 13]]
    own, outside = program_spans.charge_idle(idle, spans)
    assert own == pytest.approx({"a": 3.0, "b": 1.0, "c": 0.5, "d": 1.0,
                                 "e": 1.0})
    assert outside == pytest.approx(2.0)          # [10, 12)
    own, outside = program_spans.charge_idle([[0, 2]], [])
    assert own == {} and outside == 2


def test_readings_of_a_window():
    prog = dict(passes=4, h2d_bytes=4 * 1074003968,
                spans={"seri.h2d": {"n": 4, "total_s": 0.48,
                                    "self_s": 0.48}},
                pairs_scored=90, pairs_padded=10,
                idle_self_s={"seri.h2d": 0.4}, window_s=1.6)
    assert program_spans.readings(prog) == pytest.approx(dict(
        stage1_h2d_mb=1074.003968, stage1_h2d_ms=120.0,
        stage1_h2d_idle_share=25.0, judge_pad_share=10.0))
    # nothing ran: nothing to read
    assert program_spans.readings(dict(
        passes=0, h2d_bytes=0, spans={}, pairs_scored=0,
        pairs_padded=0)) == {}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_a_run_with_a_tracer_bound_is_correct(root):
    import served

    base = served.Served
    out = program_spans.measure("tiny-brute.swe", 2**31 + 77, 2.0, False,
                                root=root, chips=False)
    assert served.Served is base                  # unbound again
    assert out["correct"], out["compared"]
    assert out["compared"]["compiles"]["value"] == 0
    prog = out["program"]
    names = set(prog["spans"])
    assert {"embedder.embed_batch", "cache.lookup_batch", "seri.search",
            "judge.prefill", "cache.insert"} <= names
    assert names.isdisjoint(tracing.SPANS)
    assert prog["spans"]["seri.search"]["n"] == prog["passes"] > 0
    # the CPU scans with numpy: nothing is put on a device
    assert prog["h2d_bytes"] == 0 and "seri.h2d" not in names
    assert prog["stage1_h2d_mb"] == 0
    assert 0 <= prog["judge_pad_share"] < 100
