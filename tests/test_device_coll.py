"""Device (XLA/ICI-path) collectives on the virtual 8-device CPU mesh —
the single-host stand-in for a TPU slice (SURVEY.md §4 test stance)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import op as ops  # noqa: E402
from ompi_tpu import runtime  # noqa: E402
from ompi_tpu.parallel import DeviceComm, attach_mesh, make_mesh  # noqa: E402

N = 8


@pytest.fixture(scope="module", params=["8dev", "4dev", "1dev"])
def dc(request):
    """Three regimes: rank-per-device (8 devices), two rows per device
    (4 devices — the r>1 multi-device paths: block all-to-all, two-ppermute
    ring shift, local-prefix scan), and all ranks on one device (the
    single-chip bench mode)."""
    import jax as _jax
    if request.param == "8dev":
        mesh = make_mesh({"x": N})
    elif request.param == "4dev":
        mesh = make_mesh({"x": 4}, devices=_jax.devices()[:4])
    else:
        mesh = make_mesh({"x": 1}, devices=_jax.devices()[:1])
    return DeviceComm(mesh, "x")


def test_allreduce_sum(dc):
    ranks = [np.full(16, float(i + 1), np.float32) for i in range(N)]
    x = dc.from_ranks(ranks)
    out = dc.allreduce(x)
    expect = np.full(16, sum(range(1, N + 1)), np.float32)
    for row in dc.to_ranks(out):
        np.testing.assert_allclose(row, expect)


@pytest.mark.parametrize("op,npfn", [
    (ops.MAX, np.maximum), (ops.MIN, np.minimum), (ops.PROD, np.multiply),
])
def test_allreduce_ops(dc, op, npfn):
    ranks = [np.linspace(i, i + 1, 8).astype(np.float32) for i in range(N)]
    out = dc.allreduce(dc.from_ranks(ranks), op)
    expect = ranks[0]
    for r in ranks[1:]:
        expect = npfn(expect, r)
    np.testing.assert_allclose(dc.to_ranks(out)[3], expect, rtol=1e-6)


def test_bcast(dc):
    ranks = [np.full(4, float(i), np.float32) for i in range(N)]
    out = dc.bcast(dc.from_ranks(ranks), root=5)
    for row in dc.to_ranks(out):
        np.testing.assert_allclose(row, np.full(4, 5.0))


def test_allgather(dc):
    ranks = [np.array([i, 10 * i], np.int32) for i in range(N)]
    out = dc.allgather(dc.from_ranks(ranks))
    expect = np.concatenate(ranks)
    for row in dc.to_ranks(out):
        np.testing.assert_array_equal(row, expect)


def test_allgather_dedup(dc):
    """One gathered copy per DEVICE (not per rank): dim 0 = mesh position;
    ranks co-resident on a device share its row — r× less HBM than the
    canonical layout when r = R/n > 1 (round-4 verdict weak#4)."""
    ranks = [np.array([i, 10 * i], np.int32) for i in range(N)]
    out = dc.allgather_dedup(dc.from_ranks(ranks))
    ndev = dc.n
    expect = np.concatenate(ranks)
    assert out.shape == (ndev,) + expect.shape
    host = np.asarray(jax.device_get(out))
    for d in range(ndev):
        np.testing.assert_array_equal(host[d], expect)
    # per-rank views recover the canonical result without rematerializing
    views = dc.dedup_to_ranks(out, N)
    assert len(views) == N
    for v in views:
        np.testing.assert_array_equal(v, expect)


def test_reduce_scatter(dc):
    # each rank contributes N*3 elements; rank i receives reduced block i
    ranks = [np.arange(N * 3, dtype=np.float32) * (i + 1) for i in range(N)]
    out = dc.reduce_scatter(dc.from_ranks(ranks))
    total = sum(ranks)
    rows = dc.to_ranks(out)
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, total[i * 3:(i + 1) * 3])


def test_alltoall(dc):
    # rank i sends block [i, j] to rank j
    ranks = [np.stack([np.full(2, 100 * i + j, np.int32) for j in range(N)])
             for i in range(N)]
    out = dc.alltoall(dc.from_ranks(ranks))
    rows = dc.to_ranks(out)
    for j, row in enumerate(rows):
        for i in range(N):
            np.testing.assert_array_equal(row[i], np.full(2, 100 * i + j))


def test_ring_shift(dc):
    ranks = [np.array([float(i)]) for i in range(N)]
    out = dc.ring_shift(dc.from_ranks(ranks), shift=1)
    rows = dc.to_ranks(out)
    for i, row in enumerate(rows):
        assert row[0] == (i - 1) % N


def test_scan(dc):
    ranks = [np.array([float(i + 1)]) for i in range(N)]
    inc = dc.to_ranks(dc.scan(dc.from_ranks(ranks)))
    exc = dc.to_ranks(dc.scan(dc.from_ranks(ranks), exclusive=True))
    for i in range(N):
        assert inc[i][0] == sum(range(1, i + 2))
        assert exc[i][0] == (0.0 if i == 0 else sum(range(1, i + 1)))


def test_executable_cache_reuse(dc):
    x = dc.from_ranks([np.ones(32, np.float32)] * N)
    before = dc.cache_info()["entries"]
    dc.allreduce(x)
    mid = dc.cache_info()["entries"]
    dc.allreduce(x + 1)          # same shape/dtype/op → cache hit
    assert dc.cache_info()["entries"] == mid
    dc.allreduce(x.astype(jnp.bfloat16))   # new dtype → new executable
    assert dc.cache_info()["entries"] == mid + 1
    assert mid >= before


def test_barrier(dc):
    dc.barrier()   # completes without error


# -- ragged (v-variant) native device collectives --------------------------
# VERDICT r3 item 2: these previously staged to host (xla.py _to_host);
# now they are ICI programs over padded blocks + gather-map arguments.


def _ragged_rows(seed=0):
    rng = np.random.default_rng(seed)
    counts = [int(c) for c in rng.integers(1, 6, size=N)]
    rows = [np.arange(c, dtype=np.float32) + 100.0 * i
            for i, c in enumerate(counts)]
    return rows, counts


def test_allgatherv_native(dc):
    rows, counts = _ragged_rows()
    x, got_counts = dc.pad_ragged(rows)
    assert got_counts == counts
    out = dc.allgatherv(x, counts)
    expect = np.concatenate(rows)
    assert out.shape[1] == sum(counts)
    for row in dc.to_ranks(out):
        np.testing.assert_allclose(row, expect)


def test_allgatherv_cache_shared_across_counts(dc):
    """Same capacity bucket + total → one executable even when the split
    changes (the gather map travels as an argument, not a constant)."""
    x1, c1 = dc.pad_ragged([np.full(c, 1.0, np.float32)
                            for c in [2, 4, 2, 4, 2, 4, 2, 4]])
    before = dc.cache_info()["entries"]
    dc.allgatherv(x1, c1)
    mid = dc.cache_info()["entries"]
    x2, c2 = dc.pad_ragged([np.full(c, 2.0, np.float32)
                            for c in [4, 2, 4, 2, 4, 2, 4, 2]])
    out = dc.allgatherv(x2, c2)
    assert dc.cache_info()["entries"] == mid, "expected cache hit"
    np.testing.assert_allclose(
        dc.to_ranks(out)[0],
        np.concatenate([np.full(c, 2.0) for c in c2]))
    assert mid >= before


def test_gatherv_native(dc):
    rows, counts = _ragged_rows(seed=3)
    x, _ = dc.pad_ragged(rows)
    out = dc.gatherv(x, counts, root=2)
    np.testing.assert_allclose(dc.to_ranks(out)[2], np.concatenate(rows))


def test_scatter_native(dc):
    # root 3 scatters R blocks of 2 elements
    root = 3
    blocks = np.stack([np.full((2,), 10.0 * j, np.float32)
                       for j in range(N)])          # (R, 2)
    x = np.zeros((N, N, 2), np.float32)
    x[root] = blocks
    xd = dc.from_ranks(list(x))
    out = dc.scatter(xd, root=root)
    rows = dc.to_ranks(out)
    for i, row in enumerate(rows):
        np.testing.assert_allclose(row, np.full(2, 10.0 * i))


def test_scatterv_native(dc):
    root = 1
    counts = [1, 2, 3, 4, 1, 2, 3, 4]
    cap = 4
    x = np.zeros((N, N, cap), np.float32)
    for j, c in enumerate(counts):
        x[root, j, :c] = np.arange(c) + 10.0 * j
    out = dc.scatterv(dc.from_ranks(list(x)), counts, root=root)
    got = dc.unpad_ragged(out, counts)
    for j, c in enumerate(counts):
        np.testing.assert_allclose(got[j], np.arange(c) + 10.0 * j)


def test_alltoallv_native(dc):
    rng = np.random.default_rng(7)
    C = rng.integers(0, 4, size=(N, N))
    cap = int(C.max())
    x = np.zeros((N, N, cap), np.float32)
    for i in range(N):
        for j in range(N):
            x[i, j, :C[i, j]] = 1000 * i + 10 * j + np.arange(C[i, j])
    out, recv_tot = dc.alltoallv(dc.from_ranks(list(x)), C)
    assert recv_tot == [int(t) for t in C.sum(axis=0)]
    got = dc.unpad_ragged(out, recv_tot)
    for j in range(N):
        expect = np.concatenate(
            [1000 * i + 10 * j + np.arange(C[i, j]) for i in range(N)]
        ) if recv_tot[j] else np.zeros((0,))
        np.testing.assert_allclose(got[j], expect)


def test_alltoallv_cache_shared_across_routing(dc):
    """MoE regime: the routing (counts matrix) changes step to step but
    token totals are conserved, so the capacity bucket and shapes are
    stable → one executable serves every routing pattern."""
    cap = 4
    base = np.array([1, 2, 3, 2, 1, 2, 3, 2])

    def step(shift):
        # circulant counts: every column sums to base.sum() = 16 regardless
        # of shift — the "routing changed, totals conserved" shape
        C = np.stack([np.roll(base, i + shift) for i in range(N)])
        x = np.zeros((N, N, cap), np.float32)
        for i in range(N):
            for j in range(N):
                x[i, j, :C[i, j]] = i + j
        return dc.alltoallv(dc.from_ranks(list(x)), C)

    step(0)
    entries = dc.cache_info()["entries"]
    step(1)
    step(2)
    assert dc.cache_info()["entries"] == entries


def test_reduce_scatter_v_native(dc):
    counts = [1, 2, 3, 2, 1, 2, 3, 2]
    total = sum(counts)
    rows = [np.arange(total, dtype=np.float32) * (i + 1) for i in range(N)]
    x = dc.from_ranks(rows)
    out = dc.reduce_scatter_v(x, counts)
    summed = np.sum(rows, axis=0)
    displs = np.concatenate([[0], np.cumsum(counts)[:-1]])
    got = dc.unpad_ragged(out, counts)
    for i, (d, c) in enumerate(zip(displs, counts)):
        np.testing.assert_allclose(got[i], summed[int(d):int(d) + c])


def test_reduce_scatter_v_max_op(dc):
    counts = [2, 2, 2, 2, 2, 2, 2, 2]
    rows = [np.arange(16, dtype=np.float32) * ((-1) ** i) for i in range(N)]
    out = dc.reduce_scatter_v(dc.from_ranks(rows), counts, ops.MAX)
    expect = np.max(rows, axis=0)
    got = dc.unpad_ragged(out, counts)
    for i in range(N):
        np.testing.assert_allclose(got[i], expect[2 * i:2 * i + 2])


def test_xla_module_native_v_dispatch():
    """The coll/xla module routes canonical padded device layouts through
    the native ragged programs — no staged fallback, zero host transfers
    (SPC counter unchanged)."""
    def fn(ctx):
        c = ctx.comm_world
        mesh = make_mesh({"x": N})
        attach_mesh(c, mesh, "x")
        dcomm = c.device_comm
        rows, counts = _ragged_rows(seed=5)
        x, _ = dcomm.pad_ragged(rows)
        before = ctx.spc._v.get("coll_staged_fallbacks", 0)
        out = c.coll.allgatherv(c, x, counts=counts)
        C = np.full((N, N), 2, np.int64)
        xa = dcomm.from_ranks(
            [np.full((N, 2), float(i), np.float32) for i in range(N)])
        a2av = c.coll.alltoallv(c, xa, None, C, C.sum(axis=0))
        rsv = c.coll.reduce_scatter(
            c, dcomm.from_ranks([np.arange(8, dtype=np.float32)] * N),
            None, [1] * N)
        after = ctx.spc._v.get("coll_staged_fallbacks", 0)
        assert after == before, "native path must not stage"
        assert all(_is_dev(v) for v in (out, a2av, rsv))
        return (np.asarray(jax.device_get(out))[0],
                np.asarray(jax.device_get(a2av))[0],
                np.asarray(jax.device_get(rsv))[0])

    def _is_dev(v):
        return isinstance(v, jax.Array)

    out, a2av, rsv = runtime.run_ranks(1, fn)[0]
    rows, counts = _ragged_rows(seed=5)
    np.testing.assert_allclose(out, np.concatenate(rows))
    np.testing.assert_allclose(
        a2av[:16], np.repeat(np.arange(N, dtype=np.float32), 2))
    np.testing.assert_allclose(rsv, [0.0 * N * 1])


def test_comm_integration_device_dispatch():
    """A communicator with an attached mesh routes device buffers through
    coll/xla and host buffers through tuned (the check_addr dispatch)."""
    def fn(ctx):
        c = ctx.comm_world
        mesh = make_mesh({"x": N})
        attach_mesh(c, mesh, "x")
        assert c.coll.provider("allreduce") == "xla"
        # device buffer → device result
        dcomm = c.device_comm
        x = dcomm.from_ranks([np.full(4, float(i), np.float32)
                              for i in range(N)])
        dev = c.coll.allreduce(c, x)
        # host buffer → host path still works
        host = c.coll.allreduce(c, np.full(4, 2.0, np.float32))
        return (np.asarray(jax.device_get(dev))[0], host)

    dev, host = runtime.run_ranks(1, fn)[0]
    np.testing.assert_allclose(dev, np.full(4, sum(range(N)), np.float32))
    np.testing.assert_allclose(host, np.full(4, 2.0, np.float32))


def test_bfloat16_allreduce(dc):
    """bfloat16 — the TPU-native compute type — reduces natively."""
    ranks = [np.ones(128, np.float32).astype(jnp.bfloat16) * (i + 1)
             for i in range(N)]
    out = dc.allreduce(dc.from_ranks(ranks))
    np.testing.assert_allclose(
        np.asarray(dc.to_ranks(out)[0]).astype(np.float32),
        np.full(128, 36.0), rtol=1e-2)


def test_staged_fallback_entries_account_and_work():
    """Long-tail entries without native ICI programs take the explicit
    coll/accelerator staging shim on mesh comms (xla.py _to_host —
    coll_accelerator_allreduce.c:31-60 discipline): device inputs stage
    once, SPC-counted, then the host algorithm runs."""
    def fn(ctx):
        c = ctx.comm_world
        mesh = make_mesh({"x": 2}, devices=jax.devices()[:2])
        attach_mesh(c, mesh, "x")
        before = ctx.spc._v.get("coll_staged_fallbacks", 0)
        dev = jnp.full(3, float(c.rank))
        counts = [3] * c.size
        out = np.asarray(c.coll.allgatherv(c, dev, counts=counts))
        g = c.coll.gather(c, jnp.arange(2.0) + c.rank, root=0)
        after = ctx.spc._v.get("coll_staged_fallbacks", 0)
        assert after >= before + 2, (before, after)
        return out, None if g is None else np.asarray(g)

    res = runtime.run_ranks(2, fn)
    expect = np.concatenate([np.full(3, float(r)) for r in range(2)])
    for out, _g in res:
        np.testing.assert_allclose(out, expect)
    np.testing.assert_allclose(
        np.asarray(res[0][1]).reshape(2, -1),
        np.stack([np.arange(2.0) + r for r in range(2)]))


def test_intercomm_device_collectives_two_meshes():
    """Two-mesh intercomm (round-2 verdict item 5): each side attaches its
    own 4-device mesh; allreduce/bcast/allgather run their intra-group
    phase as XLA programs on that mesh (ICI), leaders bridge on the host
    path — the hierarchical two-slice shape, on the CPU fabric."""
    def fn(ctx):
        world = ctx.comm_world                  # 2 ranks: one per "slice"
        side = ctx.rank % 2
        local = world.split(side, ctx.rank)     # singleton local groups
        inter = local.create_intercomm(0, world, 1 - side)
        devs = jax.devices()[:4] if side == 0 else jax.devices()[4:]
        mesh = make_mesh({"x": 4}, devices=devs)
        from ompi_tpu.parallel import attach_mesh as am
        am(inter, mesh, "x")
        assert type(inter.coll).__name__ == "InterXlaColl"
        dc = inter.local_comm.device_comm
        # 4 resident rows on this side's mesh, value = world rank + row
        x = dc.from_ranks([np.full(8, float(ctx.rank * 10 + r), np.float32)
                           for r in range(4)])
        out = inter.coll.allreduce(inter, x)
        # remote side's local reduction: sum of (peer*10 + r) over rows
        peer = 1 - ctx.rank
        expect = np.full(8, sum(peer * 10 + r for r in range(4)),
                         np.float32)
        rows = np.asarray(jax.device_get(out))
        assert out.sharding.mesh == mesh        # stayed on OUR mesh
        np.testing.assert_allclose(rows[0], expect)
        # host buffers still take the host inter path
        host = inter.coll.allreduce(inter, np.full(4, 1.0 + ctx.rank))
        np.testing.assert_allclose(np.asarray(host),
                                   np.full(4, 1.0 + peer))
        # device allgather: concat of the remote side's rows
        g = inter.coll.allgather(inter, x)
        grow = np.asarray(jax.device_get(g))[0]
        expect_cat = np.concatenate(
            [np.full(8, float(peer * 10 + r), np.float32)
             for r in range(4)])
        np.testing.assert_allclose(grow, expect_cat)
        return True

    assert all(runtime.run_ranks(2, fn))


class TestDeviceDecision:
    """The device decision layer (VERDICT r3 item 4): per (collective,
    size) the xla module picks native-ICI vs measured host staging, with
    the same force-var + dynamic-rules-file machinery the host tuned
    component has (coll_tuned_decision_fixed.c / coll_tuned_dynamic_file.c
    applied to the device path)."""

    def _run(self, fn):
        return runtime.run_ranks(1, fn)[0]

    def test_cpu_default_stages_small_dense_alltoall(self):
        """On the CPU fabric the sweep shows staged winning dense alltoall
        below 32MB — the decision auto-selects it; allreduce stays native."""
        def fn(ctx):
            c = ctx.comm_world
            mesh = make_mesh({"x": N})
            attach_mesh(c, mesh, "x")
            dc = c.device_comm
            x = dc.from_ranks([np.stack([np.full(2, 10.0 * i + j,
                                                 np.float32)
                                         for j in range(N)])
                               for i in range(N)])
            before = ctx.spc._v.get("coll_staged_fallbacks", 0)
            out = c.coll.alltoall(c, x)
            mid = ctx.spc._v.get("coll_staged_fallbacks", 0)
            assert mid == before + 1          # staged by decision
            assert isinstance(out, jax.Array)  # ...but still device-resident
            got = np.asarray(jax.device_get(out))
            np.testing.assert_allclose(got[3][5], np.full(2, 10.0 * 5 + 3))
            r = c.coll.allreduce(
                c, dc.from_ranks([np.ones(4, np.float32)] * N))
            after = ctx.spc._v.get("coll_staged_fallbacks", 0)
            assert after == mid               # allreduce stayed native
            np.testing.assert_allclose(np.asarray(jax.device_get(r))[0],
                                       np.full(4, float(N)))
            return True

        assert self._run(fn)

    def test_force_var_overrides(self):
        from ompi_tpu.core import var

        def fn(ctx):
            c = ctx.comm_world
            mesh = make_mesh({"x": N})
            attach_mesh(c, mesh, "x")
            dc = c.device_comm
            x = dc.from_ranks([np.full(8, float(i), np.float32)
                               for i in range(N)])
            before = ctx.spc._v.get("coll_staged_fallbacks", 0)
            out = c.coll.allreduce(c, x)      # forced staged
            assert ctx.spc._v.get("coll_staged_fallbacks", 0) == before + 1
            np.testing.assert_allclose(
                np.asarray(jax.device_get(out))[2],
                np.full(8, sum(range(N))))
            return True

        var.registry.set_cli("coll_xla_allreduce_mode", "staged")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_allreduce_mode", "")
            var.registry.reset_cache()

    def test_dynamic_rules_file(self, tmp_path):
        from ompi_tpu.core import var

        rules = tmp_path / "device_rules.txt"
        rules.write_text("# device rules\n"
                         "alltoall 2 0 native\n"      # beat the cpu default
                         "allgatherv 2 0 staged\n")

        def fn(ctx):
            c = ctx.comm_world
            mesh = make_mesh({"x": N})
            attach_mesh(c, mesh, "x")
            dc = c.device_comm
            before = ctx.spc._v.get("coll_staged_fallbacks", 0)
            x = dc.from_ranks([np.stack([np.full(2, 1.0, np.float32)
                                         for _ in range(N)])
                               for _ in range(N)])
            c.coll.alltoall(c, x)             # rule says native
            assert ctx.spc._v.get("coll_staged_fallbacks", 0) == before
            xp, counts = dc.pad_ragged(
                [np.arange(i + 1, dtype=np.float32) for i in range(N)])
            out = c.coll.allgatherv(c, xp, counts=counts)  # rule: staged
            assert ctx.spc._v.get("coll_staged_fallbacks", 0) == before + 1
            np.testing.assert_allclose(
                np.asarray(jax.device_get(out))[0],
                np.concatenate([np.arange(i + 1) for i in range(N)]))
            return True

        var.registry.set_cli("coll_xla_dynamic_rules", str(rules))
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_dynamic_rules", "")
            var.registry.reset_cache()

    def test_accelerator_platform_always_native(self):
        """On a non-cpu platform the fixed default is native for EVERY
        entry (staging crosses the host bridge); checked by patching the
        platform probe — the rule the TPU run exercises for real."""
        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            mod = c.coll._entries["alltoall"]
            assert type(mod).__name__ == "XlaModule"
            mod._platform = "tpu"           # simulate the real chip
            x = c.device_comm.from_ranks(
                [np.stack([np.full(2, 1.0, np.float32)] * N)] * N)
            before = ctx.spc._v.get("coll_staged_fallbacks", 0)
            out = c.coll.alltoall(c, x)     # cpu default would stage this
            assert ctx.spc._v.get("coll_staged_fallbacks", 0) == before
            assert isinstance(out, jax.Array)
            return True

        assert self._run(fn)

    def test_coll_tune_emits_device_rules(self, tmp_path):
        from ompi_tpu.tools import coll_tune

        rows, winners = coll_tune.run_device_sweep(
            iters=2, sizes=[1024, 64 << 10])
        assert {"allreduce", "bcast", "alltoall"} <= set(winners)
        path = tmp_path / "DEVICE_RULES.txt"
        coll_tune.emit_device_rules(winners, str(path))
        text = path.read_text()
        assert "allreduce 1 0" in text
        # the emitted file parses through the decision layer's loader;
        # the sweep's winners span the full mode vocabulary (quant rows,
        # collmm bidir, rma staged) so the modes are pinned against
        # _MODES, not the native/staged pair the sweep originally knew
        from ompi_tpu.coll.xla import _MODES, _load_device_rules
        from ompi_tpu.core import var
        var.registry.set_cli("coll_xla_dynamic_rules", str(path))
        var.registry.reset_cache()
        try:
            parsed = _load_device_rules()
            assert all(r[3] in _MODES for r in parsed)
            assert any(r[0] == "allreduce" for r in parsed)
        finally:
            var.registry.set_cli("coll_xla_dynamic_rules", "")
            var.registry.reset_cache()


class TestDeviceCartNeighbor:
    """Device-native periodic-cart halo exchange: 2·ndims ppermutes
    (≙ coll_basic_neighbor_* specialized to the torus — the stencil
    workload of BASELINE.json configs[4])."""

    def _topo(self, dims):
        from ompi_tpu.topo import CartTopo
        return CartTopo(dims, [True] * len(dims))

    def test_neighbor_allgather_2d_torus(self):
        dc = DeviceComm(make_mesh({"x": N}), "x")
        topo = self._topo([2, 4])
        x = dc.from_ranks([np.full(3, float(i), np.float32)
                           for i in range(N)])
        out = dc.neighbor_allgather_cart(x, topo)     # (8, 4, 3)
        rows = np.asarray(jax.device_get(out))
        for i in range(N):
            nbrs = topo.neighbors(i)                  # [-d0, +d0, -d1, +d1]
            assert len(nbrs) == 4
            for j, nb in enumerate(nbrs):
                np.testing.assert_allclose(rows[i, j], np.full(3, float(nb)),
                                           err_msg=f"rank {i} slot {j}")

    def test_neighbor_alltoall_1d_ring(self):
        dc = DeviceComm(make_mesh({"x": N}), "x")
        topo = self._topo([N])
        # block 0 (-1 side) and block 1 (+1 side) per rank
        x = dc.from_ranks([
            np.stack([np.full(2, 100.0 * i, np.float32),       # to left
                      np.full(2, 100.0 * i + 1, np.float32)])  # to right
            for i in range(N)])
        out = dc.neighbor_alltoall_cart(x, topo)
        rows = np.asarray(jax.device_get(out))
        for i in range(N):
            left, right = (i - 1) % N, (i + 1) % N
            # slot 0 (-1): from left neighbor, ITS +1 block (toward me)
            np.testing.assert_allclose(rows[i, 0],
                                       np.full(2, 100.0 * left + 1))
            # slot 1 (+1): from right neighbor, its -1 block
            np.testing.assert_allclose(rows[i, 1],
                                       np.full(2, 100.0 * right))

    def test_halo_exchange_via_coll_dispatch(self):
        """The coll/xla module routes a canonical device layout on a
        periodic-cart mesh comm through the native exchange."""
        def fn2(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import CartTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            c.topo = CartTopo([2, 2], [True, True])
            dcomm = c.device_comm
            x = dcomm.from_ranks([np.arange(2, dtype=np.float32) + 10 * i
                                  for i in range(4)])
            dev = c.coll.neighbor_allgather(c, x)
            assert isinstance(dev, jax.Array)
            rows = np.asarray(jax.device_get(dev))
            for i in range(4):
                for j, nb in enumerate(c.topo.neighbors(i)):
                    np.testing.assert_allclose(
                        rows[i, j], np.arange(2) + 10 * nb)
            return True

        assert runtime.run_ranks(1, fn2)[0]

    def test_non_periodic_falls_back(self):
        dc = DeviceComm(make_mesh({"x": N}), "x")
        from ompi_tpu.topo import CartTopo
        topo = CartTopo([N], [False])
        x = dc.from_ranks([np.zeros(2, np.float32)] * N)
        with pytest.raises(ValueError, match="periodic"):
            dc.neighbor_allgather_cart(x, topo)

    def test_nonperiodic_cart_takes_graph_path(self):
        """Non-periodic carts route through the general graph exchange:
        boundary ranks get zero-padded slots past their (ragged) degree."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import CartTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            c.topo = CartTopo([4], [False])        # open chain
            x = c.device_comm.from_ranks(
                [np.full(2, float(i), np.float32) for i in range(4)])
            out = c.coll.neighbor_allgather(c, x)
            rows = np.asarray(jax.device_get(out))
            for i in range(4):
                nbrs = c.topo.neighbors(i)         # ragged at boundaries
                for j, nb in enumerate(nbrs):
                    np.testing.assert_allclose(rows[i, j],
                                               np.full(2, float(nb)))
                for j in range(len(nbrs), rows.shape[1]):
                    np.testing.assert_allclose(rows[i, j], 0.0)
            return True

        assert runtime.run_ranks(1, fn)[0]

    def test_graph_topology_device_exchange(self):
        """Arbitrary GraphTopo on the device path (the generality of
        coll_basic_neighbor_allgather.c, compiled)."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import GraphTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            # 0-1, 0-2, 1-3: degrees 2/2/1/1 (ragged)
            c.topo = GraphTopo(index=[2, 4, 5, 6],
                               edges=[1, 2, 0, 3, 0, 1])
            x = c.device_comm.from_ranks(
                [np.full(3, 10.0 * i, np.float32) for i in range(4)])
            out = c.coll.neighbor_allgather(c, x)
            rows = np.asarray(jax.device_get(out))
            for i in range(4):
                for j, nb in enumerate(c.topo.neighbors(i)):
                    np.testing.assert_allclose(rows[i, j],
                                               np.full(3, 10.0 * nb))
            return True

        assert runtime.run_ranks(1, fn)[0]

    def test_unservable_canonical_raises_not_hangs(self):
        """A canonical layout with NO device path (dist_graph topo) must
        raise — the host path would block forever on phantom recvs of a
        size-1 comm (the guard the graph path does not replace)."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import DistGraphTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            c.topo = DistGraphTopo(sources=[1], destinations=[2])
            x = c.device_comm.from_ranks(
                [np.zeros(2, np.float32)] * 4)
            with pytest.raises(ValueError, match="no device path"):
                c.coll.neighbor_allgather(c, x)
            return True

        assert runtime.run_ranks(1, fn)[0]

    def test_graph_neighbor_alltoall(self):
        """Directed ragged exchange: block p of rank i reaches its p-th
        out-neighbor, landing in the receiver's in-neighbor slot order."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import GraphTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            # undirected edges 0-1, 0-3, 1-2 (degrees 2/2/1/1)
            c.topo = GraphTopo(index=[2, 4, 5, 6],
                               edges=[1, 3, 0, 2, 1, 0])
            K, b = 2, 3
            # block p of rank i carries value 100*i + 10*p
            x = c.device_comm.from_ranks([
                np.stack([np.full(b, 100.0 * i + 10 * p, np.float32)
                          for p in range(K)]) for i in range(4)])
            out = c.coll.neighbor_alltoall(c, x)
            rows = np.asarray(jax.device_get(out))
            for j in range(4):
                nbrs = c.topo.in_neighbors(j)
                for k, src in enumerate(nbrs):
                    # src's block addressed to j = position of j in src's
                    # out-list
                    p = c.topo.out_neighbors(src).index(j)
                    np.testing.assert_allclose(
                        rows[j, k], np.full(b, 100.0 * src + 10 * p),
                        err_msg=f"dst {j} slot {k} (src {src})")
                for k in range(len(nbrs), rows.shape[1]):
                    # the documented contract: zeros past each in-degree
                    np.testing.assert_allclose(rows[j, k], 0.0)
            return True

        assert runtime.run_ranks(1, fn)[0]

    def test_open_cart_neighbor_alltoall_via_graph_path(self):
        """Non-periodic cart alltoall rides the graph machinery: boundary
        ranks have fewer blocks (ragged), interior ranks exchange fully."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import CartTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            c.topo = CartTopo([4], [False])
            K, b = 2, 2
            x = c.device_comm.from_ranks([
                np.stack([np.full(b, 10.0 * i + p, np.float32)
                          for p in range(K)]) for i in range(4)])
            out = c.coll.neighbor_alltoall(c, x)
            rows = np.asarray(jax.device_get(out))
            for j in range(4):
                for k, src in enumerate(c.topo.in_neighbors(j)):
                    p = c.topo.out_neighbors(src).index(j)
                    np.testing.assert_allclose(
                        rows[j, k], np.full(b, 10.0 * src + p))
            return True

        assert runtime.run_ranks(1, fn)[0]

    def test_graph_neighbor_allgatherv_ragged_rows(self):
        """Ragged per-rank contributions over the device neighborhood:
        padded rows travel whole; valid prefixes per counts."""
        def fn(ctx):
            c = ctx.comm_world
            from ompi_tpu.topo import CartTopo
            mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
            attach_mesh(c, mesh, "x")
            c.topo = CartTopo([4], [True])
            dc = c.device_comm
            rows = [np.arange(i + 1, dtype=np.float32) + 10 * i
                    for i in range(4)]
            x, counts = dc.pad_ragged(rows)
            out = c.coll.neighbor_allgatherv(c, x, counts=counts)
            got = np.asarray(jax.device_get(out))
            for j in range(4):
                for k, src in enumerate(c.topo.in_neighbors(j)):
                    valid = got[j, k, :counts[src]]
                    np.testing.assert_allclose(valid, rows[src])
                    np.testing.assert_allclose(
                        got[j, k, counts[src]:], 0.0)
            return True

        assert runtime.run_ranks(1, fn)[0]


class Test32RanksOn8Devices:
    """North-star-scale rank count (r4 verdict weak#5): R=32 rows on the
    8-device mesh — the r=4 local-fold regime at the BASELINE.json scale.
    Certifies divisibility, the executable/index caches, and the ragged
    padding caps at R=32."""

    R = 32

    def _dc(self):
        return DeviceComm(make_mesh({"x": N}), "x")

    def test_allreduce_and_bcast(self):
        dc = self._dc()
        ranks = [np.full(16, float(i + 1), np.float32) for i in range(self.R)]
        out = dc.allreduce(dc.from_ranks(ranks))
        expect = np.full(16, sum(range(1, self.R + 1)), np.float32)
        rows = dc.to_ranks(out)
        assert len(rows) == self.R
        np.testing.assert_allclose(rows[31], expect)
        b = dc.bcast(dc.from_ranks(ranks), root=17)
        np.testing.assert_allclose(dc.to_ranks(b)[3], np.full(16, 18.0))

    def test_allgather_dedup_32(self):
        dc = self._dc()
        ranks = [np.array([i, -i], np.float32) for i in range(self.R)]
        out = dc.allgather_dedup(dc.from_ranks(ranks))
        assert out.shape == (N, 2 * self.R)
        expect = np.concatenate(ranks)
        host = np.asarray(jax.device_get(out))
        for d in range(N):
            np.testing.assert_array_equal(host[d], expect)
        views = dc.dedup_to_ranks(out, self.R)
        assert len(views) == self.R
        np.testing.assert_array_equal(views[13], expect)

    def test_ragged_allgatherv_alltoallv_32(self):
        dc = self._dc()
        rng = np.random.default_rng(7)
        counts = rng.integers(1, 9, size=self.R)
        arrays = [rng.normal(size=c).astype(np.float32) for c in counts]
        x, cl = dc.pad_ragged(arrays)
        out = dc.allgatherv(x, cl)
        expect = np.concatenate(arrays)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(out))[0], expect, rtol=1e-6)
        # ragged alltoallv: circulant counts matrix at R=32
        per = 4
        vC = np.stack([np.roll(
            [(per - 1) if j % 2 == 0 else (per + 1)
             for j in range(self.R)], -i) for i in range(self.R)])
        cap = dc._bucket(int(vC.max()))
        host_rows = rng.normal(size=(self.R, per * self.R)
                               ).astype(np.float32)
        blocks = dc.pack_ragged_blocks(host_rows, vC, cap)
        xb = jax.device_put(jnp.asarray(blocks), dc.sharding())
        outb, rcounts = dc.alltoallv(xb, vC)
        got = np.asarray(jax.device_get(outb))
        assert got.shape[0] == self.R
        assert list(rcounts) == [int(c) for c in vC.sum(axis=0)]
        # spot-check rank 5's dense row: source i's block (i→5) lands at
        # offset sum(vC[:i, 5]) with the sender's packed elements
        for i in (0, 9, 31):
            send_off = int(vC[i, :5].sum())
            recv_off = int(vC[:i, 5].sum())
            c = int(vC[i, 5])
            np.testing.assert_allclose(
                got[5, recv_off:recv_off + c],
                host_rows[i, send_off:send_off + c], rtol=1e-6)


@pytest.mark.parametrize("slice_cap", [None, 2, 3, 64])
def test_alltoallv_from_rows_matches_block_form(dc, slice_cap):
    """The dense-rows sliced exchange produces EXACTLY the block-form
    alltoallv result without ever materializing the (R, R, cap) padding
    (the r4/r5 sweep-truncation shape)."""
    rng = np.random.default_rng(11)
    per = 5
    vbase = [(per - 2) if j % 2 == 0 else (per + 2) for j in range(N)]
    C = np.stack([np.roll(vbase, -i) for i in range(N)])
    rows = rng.normal(size=(N, int(C.sum(axis=1).max()))
                      ).astype(np.float32)
    cap = dc._bucket(int(C.max()))
    blocks = dc.pack_ragged_blocks(rows, C, cap)
    xb = jax.device_put(jnp.asarray(blocks), dc.sharding())
    want, want_counts = dc.alltoallv(xb, C)
    xr = jax.device_put(jnp.asarray(rows), dc.sharding())
    got, got_counts = dc.alltoallv_from_rows(xr, C, slice_cap=slice_cap)
    assert got_counts == want_counts
    np.testing.assert_allclose(np.asarray(jax.device_get(got)),
                               np.asarray(jax.device_get(want)),
                               rtol=1e-6)


def test_alltoallv_from_rows_with_elem_dims(dc):
    """EP-shaped payloads: ragged token blocks with a trailing feature
    dim route identically through the dense-rows form."""
    rng = np.random.default_rng(3)
    d = 4
    C = rng.integers(0, 4, size=(N, N))
    L = max(1, int(C.sum(axis=1).max()))
    rows = rng.normal(size=(N, L, d)).astype(np.float32)
    cap = dc._bucket(max(1, int(C.max())))
    blocks = np.zeros((N, N, cap, d), np.float32)
    for i in range(N):
        off = 0
        for j in range(N):
            c = int(C[i, j])
            blocks[i, j, :c] = rows[i, off:off + c]
            off += c
    xb = jax.device_put(jnp.asarray(blocks), dc.sharding())
    want, _ = dc.alltoallv(xb, C)
    xr = jax.device_put(jnp.asarray(rows), dc.sharding())
    got, _ = dc.alltoallv_from_rows(xr, C, slice_cap=2)
    np.testing.assert_allclose(np.asarray(jax.device_get(got)),
                               np.asarray(jax.device_get(want)),
                               rtol=1e-6)


def test_alltoallv_from_rows_cache_not_stale_across_caps(dc):
    """Same shapes + slice_cap but a LARGER max count must not reuse a
    scan executable compiled with fewer slices (it would silently zero
    the tail — caught by review in round 5; k is in the cache key)."""
    d0 = np.zeros((N, N), np.int64)
    C1 = d0 + 1
    np.fill_diagonal(C1, 2)               # max 2 → k=1 at slice_cap=2
    C2 = d0 + 1
    np.fill_diagonal(C2, 3)               # max 3 → k=2 at slice_cap=2
    L = max(int(C1.sum(axis=1).max()), int(C2.sum(axis=1).max()))
    rng = np.random.default_rng(5)
    rows = rng.normal(size=(N, L)).astype(np.float32)
    x = jax.device_put(jnp.asarray(rows), dc.sharding())
    dc.alltoallv_from_rows(x, C1, slice_cap=2)      # warm a k=1 program
    got, _ = dc.alltoallv_from_rows(x, C2, slice_cap=2)
    host = np.asarray(jax.device_get(got))
    want = DeviceComm.compact_from_rows(rows, C2, host.shape[1])
    np.testing.assert_allclose(host, want, rtol=1e-6)


def _edge_counts(name: str) -> np.ndarray:
    rng = np.random.default_rng(17)
    if name == "osu":            # the benchmark's uneven circulant split
        from benchmark.references.osu import a2av_counts
        return a2av_counts(N, 8 * 37)
    if name == "zeros":          # rank 2 sends nothing, rank 5 gets nothing
        C = rng.integers(0, 6, size=(N, N))
        C[2, :] = 0
        C[:, 5] = 0
        return C
    if name == "fills_L":        # every row ends at L, on a 1-element run:
        C = rng.integers(2, 7, size=(N, N))   # a window there would clamp
        C[:, -1] = 1
        C[:, 0] += C.sum(axis=1).max() - C.sum(axis=1)
        return C
    assert name == "multiple"    # every count a multiple of 3
    return 3 * rng.integers(0, 5, size=(N, N))


@pytest.mark.parametrize("counts,slice_cap,elem", [
    ("osu", None, ()), ("osu", 1, ()), ("osu", 3, ()), ("osu", 3, (2,)),
    ("zeros", None, ()), ("zeros", 3, (2,)),
    ("fills_L", None, ()), ("fills_L", 3, ()), ("fills_L", 1, (2,)),
    ("multiple", 3, ()), ("multiple", None, (2,)),
])
def test_alltoallv_from_rows_edges_exact(dc, counts, slice_cap, elem):
    """The per-peer contiguous slices give EXACTLY the host oracle's
    compact rows, zeros past each row's total, at the edges a slice can
    get wrong: empty senders and receivers, a segment ending at the row's
    end, counts on and off a slice boundary, trailing elem dims, and (on
    the 4- and 1-device meshes) several rank rows per device."""
    C = _edge_counts(counts)
    L = int(C.sum(axis=1).max())
    rows = (np.arange(N * L * int(np.prod(elem)), dtype=np.float32)
            .reshape((N, L) + elem) + 1.0)
    x = jax.device_put(jnp.asarray(rows), dc.sharding())
    got, got_counts = dc.alltoallv_from_rows(x, C, slice_cap=slice_cap)
    host = np.asarray(jax.device_get(got))
    assert host.shape[1] == dc.a2av_plan(rows.shape, C, slice_cap)["out_cap"]
    assert got_counts == [int(t) for t in C.sum(axis=0)]
    np.testing.assert_array_equal(
        host, DeviceComm.compact_from_rows(rows, C, host.shape[1]))


@pytest.mark.parametrize("elem", [(), (3,)], ids=["1d", "elem_dim"])
def test_alltoallv_from_rows_program_has_no_gather_or_scatter(dc, elem):
    """The segments move as contiguous slices: the compiled program holds
    no per-element gather or scatter (a scatter with non-unique indices
    lowers serially, through a sort, on the TPU)."""
    import re
    from benchmark.references.osu import a2av_counts
    C = a2av_counts(N, 8 * 64)
    shape = (N, int(C.sum(axis=1).max())) + elem
    x = jax.device_put(jnp.zeros(shape, jnp.float32), dc.sharding())
    dc.alltoallv_from_rows(x, C)
    [fn] = [f for k, f in dc._cache.items()
            if k[0] == "alltoallv_from_rows" and k[1] == shape]
    maps = dc._idx_cached(("a2av_rows", C.tobytes()), None)
    hlo = fn.lower(x, *maps).compile().as_text()
    assert not re.findall(r"(?<![\w-])(?:gather|scatter)\(", hlo)
    assert "dynamic-update-slice" in hlo


class TestCommLevelDenseRowsAlltoallv:
    """MPI's ACTUAL alltoallv buffer layout (dense rows + counts, default
    displacements) through comm.coll — routed to the sliced dense-rows
    exchange in both decision modes (round-5)."""

    def _setup(self, ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": N}), "x")
        rng = np.random.default_rng(9)
        C = rng.integers(0, 4, size=(N, N))
        L = max(1, int(C.sum(axis=1).max()))
        rows = rng.normal(size=(N, L)).astype(np.float32)
        x = jax.device_put(jnp.asarray(rows),
                           c.device_comm.sharding())
        # expected dense receive rows (the shared host oracle)
        out_cap = c.device_comm._bucket(max(1, int(C.sum(axis=0).max())))
        want = DeviceComm.compact_from_rows(rows, C, out_cap)
        return c, C, x, want

    @pytest.mark.parametrize("mode", ["native", "staged"])
    def test_dense_rows_form(self, mode, monkeypatch):
        from ompi_tpu.core import var
        monkeypatch.setenv("OMPI_TPU_coll_xla_alltoallv_mode", mode)
        var.registry.reset_cache()

        def fn(ctx):
            c, C, x, want = self._setup(ctx)
            out = c.coll.alltoallv(c, x, None, C, None)
            got = np.asarray(jax.device_get(out))
            np.testing.assert_allclose(got[:, :want.shape[1]],
                                       want[:, :got.shape[1]], rtol=1e-6)
            # recvcounts validation still applies to the dense form
            import pytest as _pytest
            with _pytest.raises(ValueError, match="recvcounts"):
                c.coll.alltoallv(c, x, None, C,
                                 np.zeros(N, np.int64) - 1)
            return True

        try:
            assert runtime.run_ranks(1, fn)[0]
        finally:
            var.registry.reset_cache()

    def test_dense_rows_with_elem_dims_comm_level(self):
        """(R, L, d) EP-shaped dense rows route through the device path
        at the comm level too (L != R disambiguates from padded blocks)."""
        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            rng = np.random.default_rng(4)
            d = 3
            C = rng.integers(1, 3, size=(N, N))
            L = int(C.sum(axis=1).max()) + 1          # ensure L != R
            if L == N:
                L += 1
            rows = rng.normal(size=(N, L, d)).astype(np.float32)
            x = jax.device_put(jnp.asarray(rows), c.device_comm.sharding())
            out = c.coll.alltoallv(c, x, None, C, None)
            got = np.asarray(jax.device_get(out))
            want = DeviceComm.compact_from_rows(rows, C, got.shape[1])
            np.testing.assert_allclose(got, want, rtol=1e-6)
            return True

        assert runtime.run_ranks(1, fn)[0]
