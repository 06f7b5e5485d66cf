"""trace.region: the framework's timed regions on the profiler's clock.

Three states: under a recording ``jax.profiler`` session a region is a
``TraceAnnotation`` in the written trace's host plane and adds count,
total and self time to ``trace.regions()``; with the ring on it records a
ring span (today's ``serve:*`` / ``build:*`` names where a site had one);
with neither it is one shared no-op that records and allocates nothing.
"""

import os
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import runtime, trace  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.parallel import DeviceComm, attach_mesh, make_mesh  # noqa: E402
from ompi_tpu.serving.engine import ServingEngine  # noqa: E402
from ompi_tpu.serving.scheduler import (ContinuousBatchingScheduler,  # noqa: E402
                                        Request)

CFG = tfm.Config(vocab=256, d_model=64, n_layers=2, n_heads=4,
                 head_dim=16, d_ff=128, dtype=jnp.float32)
COLL_PARTS = ("ompi.coll.decide", "ompi.coll.audit", "ompi.coll.launch")


@pytest.fixture(autouse=True)
def _clean():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


class _StubAnnotation:
    """Stands in for TraceAnnotation where a test needs no profiler."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recording(monkeypatch):
    """A profiler session as the region sees one."""
    trace.region("bind")           # jax is imported: binds the flag
    monkeypatch.setattr(trace, "_recording", lambda: True)
    monkeypatch.setattr(trace, "_Annotation", _StubAnnotation)


def _engine(n=2):
    mesh = make_mesh({"tp": n}, devices=jax.devices()[:n])
    dc = DeviceComm(mesh, "tp")
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    return ServingEngine(dc, tfm.shard_params(params, mesh, CFG), CFG,
                         n_pages=16, page_size=8, max_seqs=2)


def _serve(eng, n_req=2, max_new=3):
    reqs = [Request(rid=i, prompt=np.arange(5 + i, dtype=np.int32) % 200,
                    max_new=max_new) for i in range(n_req)]
    sched = ContinuousBatchingScheduler(eng, reqs)
    sched.run()
    return sched


def _xplane_events(root):
    from jax._src.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane
    pd = ProfileData.from_file(find_xplane(str(root)))
    return [(e.start_ns, e.end_ns, e.name) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_nesting_and_self_time(recording):
    with trace.region("outer"):
        time.sleep(0.002)
        with trace.region("inner"):
            time.sleep(0.004)
            with trace.region("leaf"):
                time.sleep(0.001)
        with trace.region("inner"):
            pass
    with pytest.raises(ValueError):
        with trace.region("outer"):
            with trace.region("inner"):
                raise ValueError("boom")
    r = trace.regions()
    assert {n: r[n]["count"] for n in r} == {"outer": 2, "inner": 3,
                                             "leaf": 1}
    for n in r:
        assert 0 <= r[n]["self_s"] <= r[n]["total_s"]
    # self = total less the direct children's totals, to rounding
    assert r["outer"]["self_s"] == pytest.approx(
        r["outer"]["total_s"] - r["inner"]["total_s"], abs=1e-9)
    assert r["inner"]["self_s"] == pytest.approx(
        r["inner"]["total_s"] - r["leaf"]["total_s"], abs=1e-9)
    assert r["leaf"]["self_s"] == r["leaf"]["total_s"] >= 0.001
    assert r["outer"]["self_s"] >= 0.002 and r["inner"]["self_s"] >= 0.004
    assert trace._thread().stack == []          # balanced after a raise
    assert trace.events() == []                 # the ring is off
    trace.clear()
    assert trace.regions() == {}


def test_threads_lose_no_region(recording):
    import threading
    n_threads, per = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with trace.region("ompi.coll.allreduce"):
                    with trace.region("ompi.coll.launch"):
                        pass
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    r = trace.regions()
    assert r["ompi.coll.allreduce"]["count"] == n_threads * per
    assert r["ompi.coll.launch"]["count"] == n_threads * per
    op = r["ompi.coll.allreduce"]
    assert op["self_s"] == pytest.approx(
        op["total_s"] - r["ompi.coll.launch"]["total_s"], abs=1e-6)


def test_off_path_records_nothing_and_allocates_nothing():
    assert not trace._recording() and not trace.enabled
    noop = trace.region("ompi.coll.allreduce")
    assert noop is trace._NO_REGION
    assert trace.region("ompi.engine.prefill", "serve:prefill", "serve",
                        args=None) is noop
    for _ in range(100):                        # warm every code path
        with trace.region("ompi.coll.launch"):
            pass
    before = sys.getallocatedblocks()
    for _ in range(20000):
        with trace.region("ompi.coll.launch"):
            pass
    assert sys.getallocatedblocks() - before < 50
    assert trace.events() == [] and trace.regions() == {}


def test_ring_only_keeps_span_names():
    eng = _engine()
    trace.enable(capacity=1 << 14)
    sched = _serve(eng)
    evs = trace.events()
    names = [e["name"] for e in evs]
    assert not trace._recording() and trace.regions() == {}
    # the sites that had hand-timed ring spans keep their names and args
    pre = [e for e in evs if e["name"] == "serve:prefill"]
    assert len(pre) == 2 and all(e["cat"] == "serve" for e in pre)
    assert {e["args"]["rid"] for e in pre} == {0, 1}
    assert {e["args"]["prompt_len"] for e in pre} == {5, 6}
    steps = [e for e in evs if e["name"] == "serve:decode_step"]
    assert len(steps) == sched.decode_steps >= 1
    assert steps[0]["args"]["path"] == "eager"
    builds = [e for e in evs if e["name"].startswith("build:")]
    assert builds and all(e["cat"] == "compile" for e in builds)
    assert "key" in builds[0]["args"]
    # the new regions write ring spans under their own names
    assert names.count("ompi.serve.admit") == 2
    assert names.count("ompi.engine.layer") == CFG.n_layers * (
        2 + sched.decode_steps)
    # nested regions lie on lanes of their own: no lane overlaps
    doc = trace.chrome_doc(evs, evs[0]["t"])
    lanes = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    for spans in lanes.values():
        spans.sort(key=lambda e: e["ts"])
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)


def test_regions_in_the_profilers_host_plane(tmp_path):
    eng = _engine()
    eng.dispatches = {k: 0 for k in eng.dispatches}
    calls = 3

    def coll(ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": 4}, devices=jax.devices()[:4]), "x")
        x = c.device_comm.from_ranks([np.ones(8, np.float32)] * 4)
        jax.block_until_ready(c.coll.allreduce(c, x))     # warm
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(calls):
                    jax.block_until_ready(c.coll.allreduce(c, x))
                sched = _serve(eng)
                jax.jit(lambda v: v * 7.0)(jnp.ones(3)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        return sched

    sched = runtime.run_ranks(1, coll, timeout=300)[0]
    evs = _xplane_events(tmp_path)
    win = [e for e in evs if e[2] == "bench.window"]
    assert len(win) == 1
    lo, hi = win[0][:2]
    ours = [e for e in evs if e[2].startswith("ompi.")]
    assert all(lo <= s and e <= hi for s, e, _ in ours)
    seen = {n for _, _, n in ours}
    assert {"ompi.coll.allreduce", *COLL_PARTS, "ompi.serve.admit",
            "ompi.serve.step", "ompi.engine.prefill",
            "ompi.engine.prefill.dispatch", "ompi.engine.prefill.wait",
            "ompi.engine.decode", "ompi.engine.decode.dispatch",
            "ompi.engine.decode.wait", "ompi.engine.layer",
            "ompi.engine.decode_ag", "ompi.engine.decode_rs"} <= seen
    # the table counts what the trace holds, and the calls made
    r = trace.regions()
    in_trace = {n: sum(1 for _, _, m in ours if m == n) for n in seen}
    assert {n: r[n]["count"] for n in seen} == in_trace
    assert r["ompi.coll.allreduce"]["count"] == calls
    assert all(r[n]["count"] == calls for n in COLL_PARTS[:2])
    combines = eng.dispatches["decode_ag"] + eng.dispatches["decode_rs"]
    assert r["ompi.coll.launch"]["count"] == calls + combines
    assert r["ompi.serve.admit"]["count"] == 2
    assert r["ompi.engine.prefill"]["count"] == 2
    assert r["ompi.engine.decode"]["count"] == sched.decode_steps
    assert r["ompi.serve.step"]["count"] == sched.decode_steps
    assert r["ompi.engine.layer"]["count"] == CFG.n_layers * (
        2 + sched.decode_steps)
    assert r[trace.COMPILE_REGION]["count"] >= 1
    # outside a session the table holds still
    _serve(eng, n_req=1)
    assert trace.regions()["ompi.engine.prefill"]["count"] == 2


def test_allreduce_split_sums_to_its_total(tmp_path):
    """On 4 virtual devices an allreduce's own time (hooks) plus decide,
    audit and launch is the whole ``ompi.coll.allreduce`` region."""
    def fn(ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": 4}, devices=jax.devices()[:4]), "x")
        x = c.device_comm.from_ranks([np.ones(32, np.float32)] * 4)
        jax.block_until_ready(c.coll.allreduce(c, x))
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(20):
                jax.block_until_ready(c.coll.allreduce(c, x))
        finally:
            jax.profiler.stop_trace()
        return trace.regions()

    r = runtime.run_ranks(1, fn, timeout=300)[0]
    op = r["ompi.coll.allreduce"]
    assert op["count"] == 20
    assert all(r[p]["count"] == 20 for p in COLL_PARTS)
    parts = op["self_s"] + sum(r[p]["total_s"] for p in COLL_PARTS)
    assert parts == pytest.approx(op["total_s"], rel=0.01)
    assert all(r[p]["total_s"] > 0 for p in COLL_PARTS)


def test_train_step_region(recording):
    """``make_train_step``'s timed path: one ``ompi.train.step`` region per
    dispatched step, on a mesh and off it; a step traced inside another
    jit dispatches nothing and records none."""
    tok = jnp.zeros((4, 17), jnp.int32)
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    for m in (None, mesh):
        params = tfm.init_params(jax.random.PRNGKey(0), CFG)
        if m is not None:
            params = tfm.shard_params(params, m, CFG)
        init_opt, step = tfm.make_train_step(CFG, m)
        opt = init_opt(params)
        for _ in range(2):
            params, opt, _loss = step(params, opt, tok)
        jax.jit(lambda p, o, t: step(p, o, t)[2])(params, opt, tok)
    assert trace.regions()["ompi.train.step"]["count"] == 4
