"""The trace reduction on a small recorded trace."""
import pytest

import tracing

# (start_s, end_s, "module:op", is a Pallas kernel) device ops of one
# chip, as read_events gives them, and host spans
DEV = {"/device:TPU:0": [
    (0.5, 1.5, "jit_score:fusion.1", False),              # before window
    (1.0, 2.0, "jit_ann_topk_ivf:ann_topk_ivf.1", True),
    (1.5, 2.5, "jit_score:fusion.1", False),              # overlaps
    (4.0, 5.0, "jit_ann_topk_ivf:ann_topk_ivf.1", True),
    (7.0, 8.0, "jit_encode:fusion.2", False),
    (10.5, 12.0, "jit_encode:fusion.2", False),           # past close
]}
OPS = "jit_ann_topk_ivf:ann_topk_ivf.1"
HOST = [
    (1.0, 11.0, "window"),
    (1.0, 5.5, "batch"), (1.0, 3.0, "stage1"), (3.0, 5.5, "lookup"),
    (5.5, 6.5, "insert"),
]


def test_union_clip_gaps():
    assert tracing.union([(3, 4), (1, 2), (1.5, 2.5)]) == [[1, 2.5], [3, 4]]
    assert tracing.clip([[0, 2], [3, 5]], 1, 4) == [[1, 2], [3, 4]]
    assert tracing.gaps([[1, 2], [3, 4]], 0, 5) == [[0, 1], [2, 3], [4, 5]]


def test_summary_of_the_window():
    s = tracing.summarize(DEV, HOST)
    assert s["window_s"] == 10.0
    # busy: [1, 2.5] + [4, 5] + [7, 8] + [10.5, 11]
    assert s["busy_s"] == pytest.approx(1.5 + 1 + 1 + 0.5)
    assert s["op_s"][OPS] == pytest.approx(2.0)
    assert s["op_s"]["jit_score:fusion.1"] == pytest.approx(0.5 + 1.0)
    assert s["kernels"] == [OPS]
    assert tracing.kernel_seconds(s, r"ann_topk") == pytest.approx(2.0)
    assert tracing.kernel_seconds(s, r"fusion") == 0.0   # not a kernel
    # every op of a module, kernel or not
    assert tracing.op_seconds(s, r"^jit_score:") == pytest.approx(1.5)
    assert tracing.op_seconds(s, r"^jit_") == pytest.approx(2.0 + 1.5 + 1.5)
    # idle [2.5, 4] in lookup, [5, 7] mostly insert (mid 6.0),
    # [8, 10.5] in no span
    assert s["idle_by"]["lookup"] == pytest.approx(1.5)
    assert s["idle_by"]["insert"] == pytest.approx(2.0)
    assert s["idle_by"]["no span"] == pytest.approx(2.5)
    idle = 100 * (1 - s["busy_s"] / s["window_s"])
    assert idle == pytest.approx(60.0)


def test_busy_is_averaged_over_chips():
    dev = dict(DEV)
    dev["/device:TPU:1"] = [(1.0, 11.0, "jit_x:fusion.9", False)]
    s = tracing.summarize(dev, HOST)
    assert s["busy_s"] == pytest.approx((4.0 + 10.0) / 2)
    assert s["n_chips"] == 2


def test_breakdown_lists_top_ops_and_idle():
    b = tracing.breakdown(tracing.summarize(DEV, HOST))
    assert b["device_ops"][0][0] == OPS
    assert len(b["device_ops"]) == 3
    assert b["idle_gaps"][0] == ["no span", pytest.approx(2.5)]


def test_a_loop_does_not_count_its_body_twice():
    ops = [(0.0, 10.0, "m:while.3", False), (0.0, 4.0, "m:fusion.1", False),
           (4.0, 9.0, "m:fusion.2", False), (11.0, 12.0, "m:copy.1", False)]
    assert [o[2] for o in tracing.leaves(ops)] == [
        "m:fusion.1", "m:fusion.2", "m:copy.1"]
    s = tracing.summarize({"/device:TPU:0": ops}, [(0.0, 12.0, "window")])
    assert s["busy_s"] == pytest.approx(11.0)
    assert "m:while.3" not in s["op_s"]


def test_op_names_from_hlo_text():
    name = ('%ann_topk.1 = (f32[512,8,4]{2,1,0}, s32[512,8,4]{2,1,0}) '
            'custom-call(f32[8,1024]{1,0} %q.1), '
            'custom_call_target="tpu_custom_call"')
    assert tracing._op(name) == ("ann_topk.1", True)
    assert tracing._op("%copy.9 = f32[8] copy(f32[8] %b)") == ("copy.9",
                                                               False)


def test_no_window_or_device_is_an_error():
    with pytest.raises(RuntimeError):
        tracing.summarize(DEV, [h for h in HOST if h[2] != "window"])
    with pytest.raises(RuntimeError):
        tracing.summarize({}, HOST)


def test_host_spans_are_read_from_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("embed"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    _, host = tracing.read_events(tracing.load(str(tmp_path)))
    names = sorted(h[2] for h in host)
    assert names == ["embed", "window"]
    (w,) = [h for h in host if h[2] == "window"]
    (e,) = [h for h in host if h[2] == "embed"]
    assert w[0] <= e[0] < e[1] <= w[1]
