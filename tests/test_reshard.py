"""Device-native array redistribution engine (ompi_tpu/parallel/reshard).

Acceptance pins (ISSUE 10): plan minimality — known (src, dst) pairs
compile to exactly the expected step sequences, never a blanket
gather-then-scatter; bitwise equality against the host round-trip
reference on 2/4/8-device meshes; the peak-bytes bound — every plan's
accounting stays within ``reshard_peak_factor x max(src_shard,
dst_shard)``, with the device_put fallback (not an error) when a
transition cannot be scheduled inside it; plan-cache hit/miss through
the DeviceComm-style executable cache; exactly one ``decide:reshard``
audit event per executed step; and traffic conservation — the matrix's
reshard attribution equals the audited wire bytes byte-for-byte.

NOTE the import discipline: ``ompi_tpu.parallel`` re-exports the
``reshard`` FUNCTION, shadowing the submodule attribute — module-level
state (report/reset/pvar_value) must come from
``ompi_tpu.parallel.reshard`` via from-imports.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

pytestmark = pytest.mark.reshard

from ompi_tpu import perf, runtime, trace, traffic  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.parallel import attach_mesh, make_mesh  # noqa: E402
from ompi_tpu.parallel import reshard as reshard_fn  # noqa: E402
from ompi_tpu.parallel.reshard import (  # noqa: E402
    PVARS,
    ReshardError,
    compile_plan,
    pvar_value,
    report,
    reset,
    resharder,
)

_VARS = ("traffic_enabled", "perf_enabled", "coll_xla_mode")


@pytest.fixture
def plane():
    """Clears engine/traffic/trace state around each test; set(...) routes
    vars through the CLI layer exactly like a command-line option."""
    reset()
    traffic.reset()
    perf.reset()
    trace.clear()

    def set_vars(**kw):
        for k, v in kw.items():
            var.registry.set_cli(k, str(v))
        var.registry.reset_cache()

    yield set_vars
    for name in _VARS:
        var.registry.clear_cli(name)
    var.registry.reset_cache()
    traffic.disable()
    perf.disable()
    trace.disable()
    trace.clear()
    traffic.reset()
    perf.reset()
    reset()


def _mesh(n, names=("x",), shape=None):
    devs = np.array(jax.devices()[:n])
    if shape:
        devs = devs.reshape(shape)
    return Mesh(devs, names)


def _place(host, mesh, spec):
    x = jax.device_put(host, NamedSharding(mesh, spec))
    jax.block_until_ready(x)
    return x


# -- plan minimality --------------------------------------------------------

M8 = {"x": 8}
M42 = {"x": 4, "y": 2}

PIN_CASES = [
    # (mesh axes, src, dst, expected describe(), expected wire bytes)
    (M8, P("x", None), P(None, "x"), ["all_to_all[x:0->1]"], 224),
    (M42, P("x", None), P("x", "y"), ["slice[y@1]"], 0),
    (M42, P(("x", "y"), None), P("x", None), ["all_gather[y@0]"], 256),
    # grouped axes move as ONE joint all_to_all, not per-axis steps
    (M42, P(("x", "y"), None), P(None, ("x", "y")),
     ["all_to_all[x+y:0->1]"], 224),
    (M8, P("x", None), P("x", None), [], 0),
    (M42, P("x", "y"), P(None, None),
     ["all_gather[x@0]", "all_gather[y@1]"], 1792),
]


@pytest.mark.parametrize("axes,src,dst,want,wire", PIN_CASES)
def test_plan_minimality_pins(axes, src, dst, want, wire):
    mesh = make_mesh(axes)
    plan = compile_plan((64, 8), np.float32, src, dst, mesh)
    assert plan.describe() == want
    assert plan.wire_bytes == wire
    assert not plan.fallback_reason


def test_plan_ppermute_substitution_and_exchange():
    mesh = _mesh(4, ("a", "b"), (2, 2))
    # same-size axis substitution: one ppermute, no gather
    plan = compile_plan((64, 8), np.float32, P("a", None), P("b", None),
                        mesh)
    assert plan.describe() == ["ppermute[a~b@0]"]
    # dim-pair exchange (the transpose of the mesh factors)
    plan = compile_plan((64, 8), np.float32, P("a", "b"), P("b", "a"),
                        mesh)
    assert plan.describe() == ["ppermute[a@0~b@1]"]


def test_plan_rejects_bad_specs():
    mesh = make_mesh(M8)
    with pytest.raises(ReshardError):
        compile_plan((64, 8), np.float32, P("nope", None), P(None, None),
                     mesh)
    with pytest.raises(ReshardError):
        compile_plan((64, 8), np.float32, P(None, None), P("x", "x"),
                     mesh)


# -- peak-bytes bound -------------------------------------------------------

def test_peak_bound_accounting():
    mesh = make_mesh(M42)
    for src, dst in [(P("x", None), P(None, "x")),
                     (P("x", "y"), P(None, None)),
                     (P(("x", "y"), None), P("y", "x"))]:
        plan = compile_plan((64, 8), np.float32, src, dst, mesh)
        assert plan.peak_bytes <= plan.bound_bytes
        assert plan.bound_bytes == 2 * max(plan.src_shard_bytes,
                                           plan.dst_shard_bytes)
        if plan.steps:
            assert plan.peak_bytes == max(s.in_bytes + s.out_bytes
                                          for s in plan.steps)


def test_peak_bound_breach_falls_back_to_device_put():
    mesh = make_mesh(M42)
    # factor 1.0 cannot fit any step's in+out live bytes: the compiler
    # must REPLACE the plan with the single-step device_put fallback
    # (peak = src+dst shard <= 2x max by construction), not raise
    plan = compile_plan((64, 8), np.float32, P("x", "y"), P(None, None),
                        mesh, peak_factor=1.0)
    assert [s.op for s in plan.steps] == ["device_put"]
    assert plan.fallback_reason
    assert plan.peak_bytes <= 2 * max(plan.src_shard_bytes,
                                      plan.dst_shard_bytes)


# -- bitwise round-trips on 2/4/8-device meshes -----------------------------

@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_roundtrip_bitwise(ndev, plane):
    mesh = _mesh(ndev)
    host = np.arange(64 * ndev * 6, dtype=np.float32).reshape(8 * ndev, 48)
    for src, dst in [(P("x", None), P(None, "x")),
                     (P(None, "x"), P("x", None)),
                     (P("x", None), P(None, None)),
                     (P(None, None), P("x", None))]:
        x = _place(host, mesh, src)
        y = reshard_fn(x, NamedSharding(mesh, dst))
        jax.block_until_ready(y)
        assert y.sharding.is_equivalent_to(NamedSharding(mesh, dst),
                                           y.ndim)
        assert np.array_equal(np.asarray(jax.device_get(y)), host)


def test_roundtrip_bitwise_2d_mesh(plane):
    mesh = make_mesh(M42)
    host = np.arange(64 * 48, dtype=np.float32).reshape(64, 48)
    x = _place(host, mesh, P(("x", "y"), None))
    chain = [P("x", "y"), P(None, ("x", "y")), P("y", "x"), P(None, None)]
    for spec in chain:
        x = reshard_fn(x, NamedSharding(mesh, spec))
        jax.block_until_ready(x)
        assert x.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                           x.ndim)
        assert np.array_equal(np.asarray(jax.device_get(x)), host)


def test_reshard_dst_forms(plane):
    mesh = make_mesh(M8)
    host = np.arange(64, dtype=np.float32).reshape(8, 8)
    x = _place(host, mesh, P("x", None))
    # dst may be a PartitionSpec (mesh inferred from x) or a NamedSharding
    y = reshard_fn(x, P(None, "x"))
    assert y.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "x")), y.ndim)
    z = reshard_fn(y, NamedSharding(mesh, P("x", None)))
    assert np.array_equal(np.asarray(jax.device_get(z)), host)


# -- plan cache -------------------------------------------------------------

def test_plan_cache_hit_miss(plane):
    mesh = make_mesh(M8)
    r = resharder(mesh)
    before = r.cache_info()
    p1 = r.plan((64, 8), np.dtype(np.float32), P("x", None), P(None, "x"))
    mid = r.cache_info()
    p2 = r.plan((64, 8), np.dtype(np.float32), P("x", None), P(None, "x"))
    after = r.cache_info()
    assert p1 is p2
    assert mid["plans"] == before["plans"] + 1
    assert after["plans"] == mid["plans"]            # second call: no miss
    assert after["plan_hits"] == mid["plan_hits"] + 1
    # a different shape is a different key
    r.plan((32, 8), np.dtype(np.float32), P("x", None), P(None, "x"))
    assert r.cache_info()["plans"] == after["plans"] + 1


def test_plan_counter_pvar(plane):
    mesh = make_mesh(M8)
    host = np.arange(64, dtype=np.float32).reshape(8, 8)
    x = _place(host, mesh, P("x", None))
    base = pvar_value("reshard_plans")
    jax.block_until_ready(reshard_fn(x, P(None, "x")))
    assert pvar_value("reshard_plans") == base + 1
    jax.block_until_ready(reshard_fn(x, P(None, "x")))   # cached plan
    assert pvar_value("reshard_plans") == base + 1
    assert set(PVARS) == {"reshard_plans", "reshard_steps",
                          "reshard_bytes"}


def test_spc_reads_reshard_pvars(plane):
    from ompi_tpu import spc as spc_mod
    mesh = make_mesh(M8)
    host = np.arange(64, dtype=np.float32).reshape(8, 8)
    x = _place(host, mesh, P("x", None))
    jax.block_until_ready(reshard_fn(x, P(None, "x")))
    s = spc_mod.Counters()
    snap = s.snapshot()
    for name in PVARS:
        assert snap[name] == pvar_value(name)
    assert s.get("reshard_steps") == pvar_value("reshard_steps")


# -- decision audit: one event per executed step ----------------------------

def test_one_decision_event_per_step(plane):
    plane(coll_xla_mode="native")
    trace.enable()
    mesh = make_mesh(M42)
    host = np.arange(64 * 48, dtype=np.float32).reshape(64, 48)
    x = _place(host, mesh, P("x", "y"))
    base_steps = pvar_value("reshard_steps")
    jax.block_until_ready(reshard_fn(x, NamedSharding(mesh, P(None, None))))
    steps = pvar_value("reshard_steps") - base_steps
    assert steps == 2                                 # the two gathers
    ev = [e for e in trace.events() if e.get("name") == "decide:reshard"]
    assert len(ev) == steps
    plans = {e["args"]["plan"] for e in ev}
    assert len(plans) == 1                            # both name the plan
    assert sorted(e["args"]["step"] for e in ev) == [0, 1]
    rep = report()
    assert rep["last"] is not None
    assert len(rep["last"]["steps"]) == steps


# -- traffic conservation ---------------------------------------------------

def test_traffic_conservation(plane):
    plane(traffic_enabled="true", coll_xla_mode="native")
    traffic.enable()
    mesh = make_mesh(M42)
    host = np.arange(64 * 48, dtype=np.float32).reshape(64, 48)
    x = _place(host, mesh, P(("x", "y"), None))
    base = pvar_value("reshard_bytes")
    for spec in (P(None, ("x", "y")), P("x", None), P(None, None)):
        x = reshard_fn(x, NamedSharding(mesh, spec))
        jax.block_until_ready(x)
    moved = pvar_value("reshard_bytes") - base
    assert moved > 0
    trep = traffic.report()
    edge_sum = sum(e["bytes"] for e in trep["edges"])
    assert trep["unattributed_bytes"] == 0
    assert int(trep["per_coll"].get("reshard", 0)) == moved
    assert edge_sum == moved
    assert np.array_equal(np.asarray(jax.device_get(x)), host)


# -- satellite primitives: a2a pad exactness, strided ring_shift ------------

def test_all_to_all_axis_pads_non_divisible(plane):
    from ompi_tpu.parallel.collectives import all_to_all_axis
    mesh = _mesh(4)
    host = np.arange(4 * 6, dtype=np.float32).reshape(4, 6)
    x = _place(host, mesh, P("x", None))

    def f(xs):
        return all_to_all_axis(xs, "x", split_dim=1, concat_dim=0)

    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x", None),
                              out_specs=P("x", None)))(x)
    got = np.asarray(jax.device_get(y))
    # reference: each local row pads 6 -> 8 cols, peer p receives cols
    # [2p, 2p+2); device p's output stacks every source's block
    pad = np.zeros((4, 8), np.float32)
    pad[:, :6] = host
    want = np.concatenate([pad[:, 2 * p:2 * (p + 1)] for p in range(4)],
                          axis=0)
    np.testing.assert_array_equal(got, want)
    # the padded-block convention is invertible: re-concatenating the
    # blocks and slicing off the zero tail is bit-exact
    for q in range(4):
        back = np.concatenate([got[p * 4 + q] for p in range(4)])[:6]
        np.testing.assert_array_equal(back, host[q])


def test_ring_shift_strided(plane):
    def fn(ctx):
        c = ctx.comm_world
        mesh = make_mesh(M8)
        attach_mesh(c, mesh, "x")
        d = c.device_comm
        rows = [np.array([float(i)], np.float32) for i in range(8)]
        x = d.from_ranks(rows)
        one = d.to_ranks(d.ring_shift(x, shift=2))
        two = d.to_ranks(d.ring_shift(x, shift=2, steps=2))
        try:
            d.ring_shift(x, shift=3, steps=2)
            bad = False
        except ValueError:
            bad = True
        return [np.asarray(a) for a in one], \
               [np.asarray(b) for b in two], bad

    one, two, bad = runtime.run_ranks(1, fn)[0]
    for i in range(8):
        assert one[i][0] == (i - 2) % 8          # one 2-stride hop
        np.testing.assert_array_equal(one[i], two[i])  # == two 1-hops
    assert bad                                    # 3 % 2 != 0 rejected


# -- the three call sites ---------------------------------------------------

def test_device_comm_reshard(plane):
    def fn(ctx):
        c = ctx.comm_world
        mesh = make_mesh(M8)
        attach_mesh(c, mesh, "x")
        d = c.device_comm
        host = np.arange(64, dtype=np.float32).reshape(8, 8)
        x = _place(host, mesh, P("x", None))
        y = d.reshard(x, NamedSharding(mesh, P(None, "x")))
        jax.block_until_ready(y)
        return np.asarray(jax.device_get(y))

    out = runtime.run_ranks(1, fn)[0]
    assert np.array_equal(out,
                          np.arange(64, dtype=np.float32).reshape(8, 8))


def test_ckpt_restore_onto_different_sharding(plane, tmp_path):
    from ompi_tpu import ckpt
    pytest.importorskip("orbax.checkpoint")
    mesh = make_mesh(M8)
    host = np.arange(128, dtype=np.float32).reshape(16, 8)
    state = {"w": _place(host, mesh, P("x", None))}
    ckpt.save(str(tmp_path / "c0"), state)
    like = {"w": _place(host, mesh, P(None, "x"))}
    got = ckpt.restore(str(tmp_path / "c0"), like,
                       source_sharding=NamedSharding(mesh, P("x", None)))
    assert got["w"].sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "x")), 2)
    assert np.array_equal(np.asarray(jax.device_get(got["w"])), host)
    # a GLOBAL shape mismatch is a different model: loud failure
    bad = {"w": _place(host[:8], mesh, P(None, "x"))}
    with pytest.raises(ckpt.CheckpointShapeError):
        ckpt.restore(str(tmp_path / "c0"), bad)


def test_transformer_train_decode_roundtrip(plane):
    from ompi_tpu.models.transformer import (Config, convert_params,
                                             init_params, shard_params)
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = Config(vocab=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
                 d_ff=64, seq=16)
    params = shard_params(init_params(jax.random.key(0), cfg), mesh, cfg)
    flat = jax.tree.leaves(params)
    dec = convert_params(params, mesh, cfg, to="decode")
    back = convert_params(dec, mesh, cfg, to="train")
    for a, b in zip(flat, jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(jax.device_get(a)),
                              np.asarray(jax.device_get(b)))
    with pytest.raises(ValueError):
        convert_params(params, mesh, cfg, to="serve")
