"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on hand-made events, and on a small trace recorded on the CPU."""
from __future__ import annotations

import pytest

from harness import trace


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 95, 120)]
    assert trace.merged(ev, 0, 100) == [[0, 15], [20, 30], [95, 100]]
    assert trace.busy_ns(ev, 0, 100) == 30


def test_kernel_sums_count_only_the_op_inside_the_window():
    ev = [("%fused_step_pallas.9 = (f32[1600,784]) custom-call(...)", 0, 10),
          ("%fused_step_pallas.9 = (f32[1600,784]) custom-call(...)", 20, 25),
          ("%get-tuple-element.3 = f32[] get-tuple-element("
           "%fused_step_pallas.9)", 30, 40),
          ("%fused_step_pallas.9 = (f32[1600,784]) custom-call(...)", 200,
           210)]
    summary = {"lo": 0, "hi": 100, "ops": {"dev0": ev}}
    assert trace.kernel_seconds(summary, "fused_step_pallas") == \
        pytest.approx(15e-9)
    assert trace.kernel_calls(summary, "fused_step_pallas") == 2
    (name, seconds), = trace.top_ops(ev, 0, 100, k=1)
    assert name == "%fused_step_pallas.9" and seconds == pytest.approx(15e-9)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    ev = [("op", 0, 10), ("op", 40, 50), ("op", 60, 100)]
    host = [("fit", 0, 100), ("label", 30, 58)]
    gaps = trace.idle_gaps(ev, host, 0, 100)
    assert [g[0] for g in gaps] == ["fit", "label"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 10e-9])


def test_reduction_of_a_recorded_cpu_trace():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    cap = trace.Capture()
    try:
        with cap:
            with trace.span(trace.WINDOW_SPAN):
                for _ in range(5):
                    with trace.span("fit"):
                        f(x).block_until_ready()
        tr = trace.load(cap.path, device_plane=trace.HOST_PLANE,
                        op_line="tf_XLAPjRtCpuClient")
    finally:
        cap.close()
    summary = trace.reduce(tr, {"fit"})
    assert 0 < summary["busy_s"] <= summary["window_s"]
    # the CPU marks each op's end with an event of its own
    assert trace.kernel_calls(summary, "dot_general") >= 5
    assert trace.kernel_seconds(summary, "dot_general") > 0
    gaps = summary["breakdown"]["idle_gaps"]
    assert gaps and all(label in ("fit", "none") for label, _ in gaps)
    assert any(label == "fit" for label, _ in gaps)
    assert summary["breakdown"]["device_ops"][0][1] > 0
