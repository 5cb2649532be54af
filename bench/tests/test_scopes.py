"""The program's spans and scopes in a trace (``harness.scopes``): scope
paths read from a serialized trace, leaf-op attribution, idle gaps named by
the program's spans, the readings, and the harness's existing readers left
unchanged by the larger summary."""
from __future__ import annotations

import pathlib

import pytest

from harness import peaks, scopes, spec, trace, work

PLANE = "/device:TPU:0"


def _xspace(tmp_path, ops, host) -> str:
    """A serialized trace in the profiler's own format: ``ops`` as
    (name, start_ns, end_ns, op_name) on a TPU op line, ``host`` as
    (name, start_ns, end_ns) on the host plane."""
    from jax.profiler import ProfileData

    def plane(pid, name, line, events, metadata, extra=""):
        evs = " ".join(
            f"events {{ metadata_id: {m} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int((e - s) * 1000)} }}" for m, s, e in events)
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {metadata} '
                f'{extra} }}')

    dev_meta, dev_events = [], []
    for i, (name, s, e, op_name) in enumerate(ops, start=1):
        stat = (f'stats {{ metadata_id: 7 str_value: "{op_name}" }}'
                if op_name else "")
        dev_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{name}" {stat} }} }}')
        dev_events.append((i, s, e))
    host_meta, host_events = [], []
    for i, (name, s, e) in enumerate(host, start=1):
        host_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{name}" }} }}')
        host_events.append((i, s, e))
    text = (plane(1, PLANE, trace.OP_LINE, dev_events, " ".join(dev_meta),
                  'stat_metadata { key: 7 value { id: 7 name: "tf_op" } }')
            + plane(2, trace.HOST_PLANE, "python", host_events,
                    " ".join(host_meta)))
    path = pathlib.Path(tmp_path) / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


#: A fit: the bench's fit span around topomap.fit, backend.run inside it,
#: then topomap.label.
HOST = [(trace.WINDOW_SPAN, 0, 1000), ("fit", 0, 900),
        ("topomap.fit", 2, 600), ("backend.run", 50, 550),
        ("topomap.label", 610, 700)]
OPS = [("%while.1 = (f32[]) while()", 60, 540, "jit(f)/while:"),
       ("%fusion.3 = f32[] fusion()", 60, 160,
        "jit(f)/while/body/afm.cascade/add:"),
       ("%fusion.4 = f32[] fusion()", 200, 300,
        "jit(f)/while/body/fused.wave_keys/threefry2x32:"),
       ("%bmu_pallas.1 = f32[] custom-call()", 300, 400,
        "jit(f)/while/body/afm.search/jit(bmu_pallas)/pallas_call:"),
       ("%copy.2 = f32[] copy()", 450, 500,
        "jit(f)/while/body/events.pool/copy:"),
       ("%fusion.9 = f32[] fusion()", 620, 650, "jit(g)/reduce:"),
       ("%fusion.10 = f32[] fusion()", 700, 900, "")]


@pytest.fixture
def summary(tmp_path):
    tr = scopes.load(_xspace(tmp_path, OPS, HOST))
    return scopes.reduce(tr, {"fit"})


def test_scope_paths_line_up_with_the_ops_of_a_serialized_trace(tmp_path):
    tr = scopes.load(_xspace(tmp_path, OPS, HOST))
    assert [op[0] for op in tr["devices"][PLANE]] == [o[0] for o in OPS]
    assert tr["scopes"][PLANE] == [o[3] for o in OPS]
    assert tr["host"] == [(n, float(s), float(e)) for n, s, e in HOST]


def test_a_fusion_counts_under_its_own_scope_and_control_flow_under_none(
        summary):
    # the while op spans the whole loop under no program scope; the fusion
    # whose root was traced under afm.cascade counts there, and only there
    assert scopes.scope_s(summary, "afm.cascade") == pytest.approx(100e-9)
    assert scopes.scope_s(summary, "afm.adapt") == 0
    assert scopes.in_scope("jit(f)/afm.cascade/add:", "afm.cascade")
    assert not scopes.in_scope("jit(f)/afm.cascade_x/add:", "afm.cascade")
    # leaf ops only: 100 + 100 + 100 + 50 inside backend.run
    assert scopes.leaf_busy_s(summary, "backend.run") == pytest.approx(350e-9)
    assert scopes.leaf_busy_s(summary) == pytest.approx(580e-9)


def test_idle_gaps_are_named_by_the_innermost_program_span(summary):
    # the while op counts busy, as device_idle counts it: the gaps are
    # 0-60 and 540-620 (topomap.fit, inside the bench's fit span), 650-700
    # (topomap.label) and 900-1000 (no span open)
    labels = {label for label, _ in summary["breakdown"]["idle_gaps"]}
    assert labels == {"topomap.fit", "topomap.label", "none"}
    idle = scopes.idle_by_span(summary)
    assert idle == pytest.approx({"topomap.fit": 140e-9,
                                  "topomap.label": 50e-9, "none": 100e-9})
    assert sum(idle.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


@pytest.mark.parametrize("name, counters, want", [
    ("train.loop_idle", None, 100 * (1 - 350 / 500)),
    ("fused.key_chain_share", None, 100 * 100 / 350),
    ("train.cascade_share", None, 100 * 100 / 350),
    ("async.pool_share", None, 100 * 50 / 580),
    ("serve.queue_ms", {"queued_s": 0.006, "dispatch_requests": 4}, 1.5),
])
def test_each_reading_on_a_hand_built_trace(summary, name, counters, want):
    assert scopes.readings(summary, counters)[name] == pytest.approx(want)


def test_dispatch_reading_is_the_mean_gateway_dispatch_span(tmp_path):
    host = [(trace.WINDOW_SPAN, 0, 1200), ("gateway.dispatch", 100, 300),
            ("engine.bmu", 150, 250), ("gateway.dispatch", 600, 900)]
    ops = [("%copy.1 = f32[] copy()", 0, 100, ""),
           ("%bmu_pallas.1 = f32[] custom-call()", 160, 240, ""),
           ("%copy.2 = f32[] copy()", 250, 600, "")]
    s = scopes.reduce(scopes.load(_xspace(tmp_path, ops, host)), set())
    got = scopes.readings(s)
    assert got["serve.dispatch_ms"] == pytest.approx(250e-9 * 1e3)
    # no loop, no scope and no counter: nothing else is read
    assert set(got) == {"serve.dispatch_ms"}
    assert {g[0] for g in s["breakdown"]["idle_gaps"]} == {
        "gateway.dispatch", "engine.bmu", "none"}


def test_a_trace_without_program_spans_or_scopes_reads_nothing(tmp_path):
    ops = [(name, s, e, "") for name, s, e, _ in OPS]
    host = [(trace.WINDOW_SPAN, 0, 1000), ("fit", 0, 900)]
    s = scopes.reduce(scopes.load(_xspace(tmp_path, ops, host)), {"fit"})
    assert scopes.readings(s, {"queued_s": None,
                               "dispatch_requests": 3}) == {}


def _ctx(tr: dict) -> dict:
    counters = {"samples": 64_000, "steps": 4_000, "receipts": 90_000,
                "waves": 23_000, "window_s": 1e-6, "requested": 400,
                "padded": 800, "dispatches": 10, "dispatch_requests": 12,
                "rounds": 320}
    return {"counters": counters, "trace": tr,
            "peaks": peaks.peaks_for("TPU v5 lite"), "work": work,
            "afm": {"side": 40, "dim": 784, "batch": 16}, "raw": {}}


def test_every_existing_reader_reads_the_same_from_the_larger_summary(
        tmp_path):
    path = _xspace(tmp_path, OPS + [
        ("%fused_step_pallas.9 = f32[] custom-call()", 700, 800, "")], HOST)
    plain = trace.reduce(trace.load(path), {"fit"})
    larger = scopes.reduce(scopes.load(path), {"fit"})
    assert set(plain) < set(larger)
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert names
    for name in names:
        read = spec.metric_reader(name)
        assert read(_ctx(larger)) == read(_ctx(plain)), name


def test_program_spans_of_a_recorded_cpu_fit():
    import jax
    import numpy as np
    from repro.api import TopoMap

    x = np.random.default_rng(0).random((64, 8), np.float32)
    tm = TopoMap(side=4, dim=8, i_max=64, batch=4)
    tm.fit(x, key=jax.random.PRNGKey(0))
    cap = trace.Capture()
    try:
        with cap:
            with trace.span(trace.WINDOW_SPAN):
                tm.fit(x, key=jax.random.PRNGKey(1))
        tr = scopes.load(cap.path, device_plane=trace.HOST_PLANE,
                         op_line="tf_XLAPjRtCpuClient")
    finally:
        cap.close()
    s = scopes.reduce(tr, set())
    assert len(scopes.spans(s, "topomap.fit")) == 1
    assert len(scopes.spans(s, "backend.run")) == 1
    assert 0 <= scopes.readings(s)["train.loop_idle"] <= 100


def test_top_ops_carry_their_scope_paths(summary):
    top = scopes.top_ops(summary, k=2)
    assert [t[0] for t in top] == ["%fusion.10", "%fusion.3"]
    assert top[1][2] == "jit(f)/while/body/afm.cascade/add:"
    assert all(not t[0].startswith("%while") for t in scopes.top_ops(summary))
