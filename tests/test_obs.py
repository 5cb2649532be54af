"""The program's host spans and device scopes (``repro.obs``): the spans
nest as documented in a CPU profile, the scopes reach the compiled HLO's
``op_name`` metadata, and the gateway counts its queueing time."""
from __future__ import annotations

import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import TopoMap, backends
from repro.core import afm as afm_lib
from repro.core import events
from repro.core.afm import AFMConfig
from repro.core.placement import base as placement_base
from repro.serving import MapGateway, MapService

CFG = AFMConfig(side=4, dim=8, i_max=64, batch=4)


def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every event on the host plane."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events)
    return out


def _nested(spans, outer: str, inner: str) -> bool:
    return any(o[1] <= i[1] and i[2] <= o[2]
               for o in spans if o[0] == outer
               for i in spans if i[0] == inner)


def test_host_spans_nest_in_a_cpu_profile(tmp_path):
    x = np.random.default_rng(0).random((64, 8), np.float32)
    y = np.arange(64) % 3
    tm = TopoMap(CFG, backend="batched")
    tm.fit(x, key=jax.random.PRNGKey(1))            # compiles outside
    with MapGateway(max_delay=0.001) as gw:
        gw.attach("m", MapService.from_estimator(tm.label(x, y)))
        gw.submit("m", x[:2]).result()
        jax.profiler.start_trace(str(tmp_path))
        try:
            tm.fit(x, key=jax.random.PRNGKey(2))
            tm.label(x, y)
            gw.submit("m", x[:3], kind="predict").result()
        finally:
            jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert _nested(spans, obs.TOPOMAP_FIT, obs.BACKEND_RUN)
    assert any(s[0] == obs.TOPOMAP_LABEL for s in spans)
    for inner in (obs.GATEWAY_MERGE, obs.ENGINE_BMU, obs.GATEWAY_RESOLVE):
        assert _nested(spans, obs.GATEWAY_DISPATCH, inner), inner
    dispatch, = [s for s in spans if s[0] == obs.GATEWAY_DISPATCH]
    assert dispatch[3] == {"requests": 1, "rows": 3}


def _pallas_step(kernel: str):
    be = backends.get_backend("pallas", CFG, kernel=kernel,
                              use_pallas=True, interpret=True)
    return jax.jit(be.step), (be.init(jax.random.PRNGKey(0), None),
                              jnp.ones((CFG.batch, CFG.dim)),
                              jax.random.PRNGKey(1))


def _event_engine():
    cfg = AFMConfig(side=4, dim=8, i_max=64, batch=1)
    ecfg = events.EventConfig(latency="exponential", delay=1.0)
    e = 4
    fn = events._compiled_runner(
        cfg, ecfg, e, afm_lib.search_exact, events._default_p,
        events._default_l_c, False, placement_base.resolve_placement(None))
    state = afm_lib.init(jax.random.PRNGKey(0), cfg)
    return fn, (state, jnp.ones((e, cfg.dim)),
                jax.random.split(jax.random.PRNGKey(1), e),
                jax.random.PRNGKey(2))


@pytest.mark.parametrize("build, scopes", [
    (functools.partial(_pallas_step, "staged"),
     (obs.AFM_SEARCH, obs.AFM_ADAPT, obs.AFM_CASCADE)),
    (functools.partial(_pallas_step, "fused"), (obs.FUSED_WAVE_KEYS,)),
    (_event_engine, (obs.EVENTS_POOL, obs.EVENTS_DELIVER)),
], ids=["staged", "fused", "events"])
def test_device_scopes_reach_the_hlo_op_name_metadata(build, scopes):
    fn, args = build()
    hlo = fn.lower(*args).compile().as_text()
    names = [line for line in hlo.splitlines() if "op_name=" in line]
    for scope in scopes:
        assert any(f"/{scope}/" in line for line in names), scope


def test_queued_s_counts_the_coalescing_wait_of_a_lone_request():
    x = np.random.default_rng(0).random((64, 8), np.float32)
    tm = TopoMap(CFG, backend="batched").fit(x, key=jax.random.PRNGKey(1))
    svc = MapService.from_estimator(tm)
    with MapGateway(max_delay=0.02, coalesce_max=16) as gw:
        gw.attach("m", svc)
        gw.submit("m", x[:1]).result()              # compiles the bucket
        before = gw.stats.queued_s
        gw.submit("m", x[:2]).result()
        waited = gw.stats.queued_s - before
        # a lone request is held until its deadline or the stall grace
        assert waited >= min(gw.max_delay, gw._stall_wait)
        gw.submit("m", x[:16]).result()             # inline: never queued
        assert gw.stats.queued_s == before + waited
        assert gw.stats.dispatch_requests == 2
