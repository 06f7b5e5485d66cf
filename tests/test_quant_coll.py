"""Block-quantized device collectives (coll/quant) — numerics vs the f32
reference, adversarial inputs, guard rails, executable-cache behavior, and
the native/staged/quant decision layer, on the virtual 8-device CPU mesh
(the single-host stand-in for a TPU slice, SURVEY.md §4 test stance)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import op as ops  # noqa: E402
from ompi_tpu import runtime  # noqa: E402
from ompi_tpu.coll import quant  # noqa: E402
from ompi_tpu.parallel import DeviceComm, attach_mesh, make_mesh  # noqa: E402

N = 8


@pytest.fixture(scope="module", params=[8, 4, 2])
def dc(request):
    """8 ranks over 8/4/2 devices — rank-per-device plus the r>1
    local-fold regimes (co-resident rows must fold exactly in f32
    before anything touches the quantized wire)."""
    n = request.param
    mesh = make_mesh({"x": n}, devices=jax.devices()[:n])
    return DeviceComm(mesh, "x")


def _rows(count, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((N, count)).astype(dtype)


def _put(dc, host, dtype=None):
    x = jnp.asarray(host)
    if dtype is not None:
        x = x.astype(dtype)
    return jax.device_put(x, dc.sharding())


def _rel_err(got, ref):
    scale = float(np.max(np.abs(ref))) or 1.0
    return float(np.max(np.abs(got.astype(np.float64)
                               - ref.astype(np.float64)))) / scale


def _snr_db(got, ref):
    noise = float(np.sum((got.astype(np.float64)
                          - ref.astype(np.float64)) ** 2))
    return 10 * np.log10(float(np.sum(ref.astype(np.float64) ** 2))
                         / max(noise, 1e-30))


# -- numerics vs the f32 reference ------------------------------------------

@pytest.mark.parametrize("block", [64, 256])
def test_allreduce_f32_error_bound(dc, block):
    host = _rows(4096)
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host), block=block)))
    ref = host.sum(axis=0, dtype=np.float32)
    for row in out:
        assert _rel_err(row, ref) <= 1e-2
    assert _snr_db(out[0], ref) >= 30.0


def test_allreduce_bf16(dc):
    host = _rows(2048, seed=1)
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host, jnp.bfloat16))).astype(
            np.float32))
    ref = host.sum(axis=0, dtype=np.float32)
    # bf16's own 8-bit mantissa stacks on the two quantization roundings
    assert _rel_err(out[0], ref) <= 3e-2


def test_allreduce_bf16_scales(dc):
    """bf16 per-block scales halve the scale traffic; error stays in the
    same class (the scale's 8-bit mantissa adds ~0.4% multiplicative)."""
    host = _rows(2048, seed=2)
    out = np.asarray(jax.device_get(dc.quant.allreduce(
        _put(dc, host), scale_dtype="bfloat16")))
    ref = host.sum(axis=0, dtype=np.float32)
    assert _rel_err(out[0], ref) <= 2e-2


def test_allreduce_avg(dc):
    host = _rows(1024, seed=3)
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host), ops.AVG)))
    ref = host.mean(axis=0, dtype=np.float32)
    # same two roundings as SUM; the max-abs statistic sits right at the
    # 1e-2 class, so the bound carries a small headroom factor
    assert _rel_err(out[0], ref) <= 1.5e-2


def test_reduce_scatter_f32_never_requantized(dc):
    """The reduce_scatter result is the f32 accumulation of dequantized
    contributions — one rounding on the data path, so it is strictly
    more accurate than the full allreduce."""
    b = 512
    host = _rows(N * b, seed=4)
    out = np.asarray(jax.device_get(
        dc.quant.reduce_scatter(_put(dc, host))))
    ref = host.sum(axis=0, dtype=np.float32).reshape(N, b)
    assert out.shape == (N, b)
    assert _rel_err(out, ref) <= 1e-2


def test_allgather(dc):
    b = 256
    host = _rows(b, seed=5)
    out = np.asarray(jax.device_get(dc.quant.allgather(_put(dc, host))))
    ref = host.reshape(N * b)
    assert out.shape == (N, N * b)
    for row in out:
        assert _rel_err(row, ref) <= 1e-2


def test_psum_quant_inside_shard_map():
    """The gradient-sync primitive: psum_quant inside a user shard_map
    matches the exact psum to quantization tolerance."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"x": N})
    host = _rows(300, seed=6)

    def body(x):
        return quant.psum_quant(x[0], "x", N, avg=True, block=64)[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                               out_specs=P("x")))
    out = np.asarray(jax.device_get(fn(jnp.asarray(host))))
    ref = host.mean(axis=0, dtype=np.float32)
    for row in out:
        assert _rel_err(row, ref) <= 1e-2


# -- adversarial inputs -----------------------------------------------------

def test_outlier_block_isolation(dc):
    """A 1e4 spike only poisons its OWN 256-element block — every other
    block keeps unit-scale accuracy. This is the point of per-block
    scales vs one tensor-wide scale."""
    host = _rows(2048, seed=7)
    host[0, 10] = 1.0e4
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host))))[0]
    ref = host.sum(axis=0, dtype=np.float32)
    clean = slice(256, None)                  # blocks 1.. have no spike
    err = np.max(np.abs(out[clean] - ref[clean]))
    # unit-scale data: absolute error stays in the unit-scale class
    assert err <= 0.2
    # the spike itself survives to ~1e-2 relative
    assert abs(out[10] - ref[10]) / abs(ref[10]) <= 1e-2


def test_all_zero_blocks_exact(dc):
    host = np.zeros((N, 1024), np.float32)
    host[:, 512:] = _rows(512, seed=8)[:, :]
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host))))[0]
    # zero blocks come back EXACTLY zero (scale 0, safe divisor)
    np.testing.assert_array_equal(out[:512], 0.0)
    assert np.isfinite(out).all()


def test_denormal_inputs_finite(dc):
    """Subnormal inputs never produce NaN/Inf: either they survive the
    quantized path or the backend's flush-to-zero zeroes them (XLA CPU
    flushes f32 subnormals) — both land within an absolute epsilon of
    the reference, and nothing blows up in the x/scale division."""
    host = np.full((N, 512), 1e-40, np.float32)
    out = np.asarray(jax.device_get(
        dc.quant.allreduce(_put(dc, host))))[0]
    assert np.isfinite(out).all()
    ref = host.sum(axis=0, dtype=np.float32)
    assert float(np.max(np.abs(out - ref))) <= 1e-38


def test_quantize_roundtrip_error_model():
    """Per-element |x - deq(q(x))| <= amax/254 + ulp — the error model the
    module docstring advertises."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((4, 1024)).astype(np.float32))
    q, s = quant.quantize_blocks(x, 256)
    back = quant.dequantize_blocks(q, s, 256)
    err = np.asarray(jnp.abs(back - x)).reshape(4, 4, 256)
    amax = np.asarray(jnp.abs(x)).reshape(4, 4, 256).max(axis=-1)
    assert (err.max(axis=-1) <= amax / 250.0 + 1e-7).all()


# -- guard rails (loud failure, no silent fallthrough) ----------------------

@pytest.mark.parametrize("op", [ops.MAX, ops.MIN, ops.PROD, ops.BAND,
                                ops.MAXLOC, ops.MINLOC])
def test_reject_non_sum_ops(op):
    with pytest.raises(ValueError):
        quant.check_quantizable(op, np.float32)


@pytest.mark.parametrize("dtype", [np.int32, np.int8, np.bool_])
def test_reject_non_float_dtypes(dtype):
    with pytest.raises(ValueError):
        quant.check_quantizable(ops.SUM, dtype)


def test_engine_rejects_int_input(dc):
    x = _put(dc, np.ones((N, 256), np.int32))
    with pytest.raises(ValueError):
        dc.quant.allreduce(x)
    with pytest.raises(ValueError):
        dc.quant.reduce_scatter(_put(dc, np.ones((N, N * 4), np.int32)))


def test_engine_rejects_bad_op(dc):
    x = _put(dc, np.ones((N, 256), np.float32))
    with pytest.raises(ValueError):
        dc.quant.allreduce(x, ops.MAX)


def test_bad_scale_dtype():
    with pytest.raises(ValueError):
        quant._params(256, "float16")


# -- byte accounting --------------------------------------------------------

def test_wire_ratio_at_1mib():
    """The headline contract: >= 1 MiB/rank f32 traffic moves <= 0.3x the
    native bytes through the quantized arm (int8 payload + one f32
    scale per 256 elements = 0.2539x)."""
    for coll in ("allreduce", "reduce_scatter", "allgather"):
        wb = quant.wire_bytes(coll, 1 << 18, 8, np.float32)
        assert wb["ratio"] <= 0.3, (coll, wb)
        assert wb["quant_bytes"] < wb["native_bytes"]


def test_wire_ratio_unknown_coll():
    with pytest.raises(ValueError):
        quant.wire_bytes("alltoall", 1024, 8, np.float32)


def test_padded_len():
    assert quant.padded_len(1, 8, 256) == 2048
    assert quant.padded_len(2048, 8, 256) == 2048
    assert quant.padded_len(2049, 8, 256) == 4096


# -- executable cache -------------------------------------------------------

def test_cache_shared_within_bucket(dc):
    """Shapes padding to the same (n x block) unit count share ONE
    executable — padding happens outside the cached program."""
    dc.quant.allreduce(_put(dc, _rows(1000)))
    mid = dc.cache_info()["entries"]
    dc.quant.allreduce(_put(dc, _rows(900, seed=1)))      # same bucket
    assert dc.cache_info()["entries"] == mid
    dc.quant.allreduce(_put(dc, _rows(1000)), block=128)  # new program
    assert dc.cache_info()["entries"] == mid + 1


def test_hlo_host_transfer_free(dc):
    """Compile-level evidence the quantized program never leaves the
    device plane: zero host custom-calls in the lowered HLO."""
    host = _rows(512, seed=10)
    x = _put(dc, host)
    dc.quant.allreduce(x)
    key = ("quant_allreduce", "sum", N,
           quant.padded_len(512, dc.n, 256), "float32", 256,
           "float32", dc.n)
    assert key in dc._cache
    padded = dc.quant._padded(
        x, 512, quant.padded_len(512, dc.n, 256))
    hlo = dc._cache[key].lower(padded).compile().as_text()
    bad = [ln for ln in hlo.splitlines()
           if "custom-call" in ln and "host" in ln.lower()]
    assert not bad, bad


# -- decision layer (native | staged | quant third arm) ---------------------

class TestQuantDecision:
    def _run(self, fn):
        return runtime.run_ranks(1, fn)[0]

    def test_default_is_exact(self):
        """Out of the box the quantized arm NEVER carries traffic — the
        conservative default ISSUE acceptance demands."""
        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            x = dcomm.from_ranks([np.full(64, float(i), np.float32)
                                  for i in range(N)])
            out = c.coll.allreduce(c, x)
            assert ctx.spc._v.get("device_quant_collectives", 0) == 0
            np.testing.assert_allclose(
                np.asarray(jax.device_get(out))[0],
                np.full(64, sum(range(N))))
            return True

        assert self._run(fn)

    def test_per_entry_force(self):
        from ompi_tpu.core import var

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            host = _rows(512, seed=11)
            out = c.coll.allreduce(c, _put(dcomm, host))
            assert ctx.spc._v.get("device_quant_collectives", 0) == 1
            ref = host.sum(axis=0, dtype=np.float32)
            assert _rel_err(np.asarray(jax.device_get(out))[0],
                            ref) <= 1e-2
            return True

        var.registry.set_cli("coll_xla_allreduce_mode", "quant")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_allreduce_mode", "")
            var.registry.reset_cache()

    def test_per_entry_force_bad_dtype_raises(self):
        from ompi_tpu.core import var

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            x = c.device_comm.from_ranks(
                [np.ones(16, np.int32)] * N)
            with pytest.raises(ValueError):
                c.coll.allreduce(c, x)
            return True

        var.registry.set_cli("coll_xla_allreduce_mode", "quant")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_allreduce_mode", "")
            var.registry.reset_cache()

    def test_blanket_switch_int_rides_exact(self):
        """OMPI_TPU_COLL_QUANT=on upgrades eligible float traffic and
        leaves ineligible (int) traffic on the exact path — blanket on
        is a preference, not a force-or-fail."""
        from ompi_tpu.core import var

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            host = _rows(512, seed=12)
            c.coll.allreduce(c, _put(dcomm, host))
            assert ctx.spc._v.get("device_quant_collectives", 0) == 1
            xi = dcomm.from_ranks([np.ones(16, np.int32)] * N)
            out = c.coll.allreduce(c, xi)       # ineligible: exact path
            assert ctx.spc._v.get("device_quant_collectives", 0) == 1
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(out))[0], np.full(16, N))
            return True

        var.registry.set_cli("COLL_QUANT", "on")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("COLL_QUANT", "")
            var.registry.reset_cache()

    def test_rules_respect_size_floor(self, tmp_path):
        """A measured quant rule only fires at >= coll_quant_min_bytes —
        small reductions are latency-bound and keep the exact path."""
        from ompi_tpu.core import var

        rules = tmp_path / "rules.txt"
        rules.write_text("allreduce 1 0 quant\n")

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            small = _put(dcomm, _rows(64, seed=13))   # 256 B/rank
            c.coll.allreduce(c, small)
            assert ctx.spc._v.get("device_quant_collectives", 0) == 0
            return True

        var.registry.set_cli("coll_xla_dynamic_rules", str(rules))
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_dynamic_rules", "")
            var.registry.reset_cache()

    def test_rules_pick_quant_over_floor(self, tmp_path):
        from ompi_tpu.core import var

        rules = tmp_path / "rules.txt"
        rules.write_text("allreduce 1 0 quant\n")

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            host = _rows(512, seed=14)                # 2 KiB/rank
            out = c.coll.allreduce(c, _put(dcomm, host))
            assert ctx.spc._v.get("device_quant_collectives", 0) == 1
            ref = host.sum(axis=0, dtype=np.float32)
            assert _rel_err(np.asarray(jax.device_get(out))[0],
                            ref) <= 1e-2
            return True

        var.registry.set_cli("coll_xla_dynamic_rules", str(rules))
        var.registry.set_cli("coll_quant_min_bytes", "1024")
        # 512 elems / 8 ranks = 64-elem shards: at the default block of
        # 256 the padding pushes the quant wire PAST native and the
        # pad-past-native veto (rightly) refuses the rule row — tune the
        # block down so the rule row is genuinely eligible here.
        var.registry.set_cli("coll_quant_block", "64")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_dynamic_rules", "")
            var.registry.clear_cli("coll_quant_min_bytes")
            var.registry.clear_cli("coll_quant_block")
            var.registry.reset_cache()

    def test_blanket_off_vetoes_rules(self, tmp_path):
        from ompi_tpu.core import var

        rules = tmp_path / "rules.txt"
        rules.write_text("allreduce 1 0 quant\n")

        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": N}), "x")
            dcomm = c.device_comm
            c.coll.allreduce(c, _put(dcomm, _rows(512, seed=15)))
            assert ctx.spc._v.get("device_quant_collectives", 0) == 0
            return True

        var.registry.set_cli("coll_xla_dynamic_rules", str(rules))
        var.registry.set_cli("coll_quant_min_bytes", "1024")
        var.registry.set_cli("COLL_QUANT", "off")
        var.registry.reset_cache()
        try:
            assert self._run(fn)
        finally:
            var.registry.set_cli("coll_xla_dynamic_rules", "")
            var.registry.clear_cli("coll_quant_min_bytes")
            var.registry.set_cli("COLL_QUANT", "")
            var.registry.reset_cache()


# -- the Config-level gradient-sync lever -----------------------------------

def test_transformer_grad_sync_quant():
    pytest.importorskip("optax")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ompi_tpu.models.transformer import (Config, init_params,
                                             make_train_step, shard_params)

    mesh = make_mesh({"dp": N})
    cfg = Config(vocab=64, d_model=32, n_layers=1, n_heads=2, head_dim=16,
                 d_ff=64, seq=16, dtype=jnp.float32, grad_sync="quant",
                 grad_sync_block=64)
    params = shard_params(init_params(jax.random.PRNGKey(0), cfg),
                          mesh, cfg)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (N, 17), 0, 64),
        NamedSharding(mesh, P("dp", None)))
    init_opt, step = make_train_step(cfg, mesh)
    params, _, loss = step(params, init_opt(params), tokens)
    assert np.isfinite(float(loss))

    # the exact arm on the same batch agrees to quantization tolerance
    cfg_n = Config(vocab=64, d_model=32, n_layers=1, n_heads=2,
                   head_dim=16, d_ff=64, seq=16, dtype=jnp.float32)
    params_n = shard_params(init_params(jax.random.PRNGKey(0), cfg_n),
                            mesh, cfg_n)
    init_n, step_n = make_train_step(cfg_n, mesh)
    _, _, loss_n = step_n(params_n, init_n(params_n), tokens)
    assert abs(float(loss) - float(loss_n)) <= 1e-3


def test_transformer_grad_sync_guards():
    pytest.importorskip("optax")
    from ompi_tpu.models.transformer import Config, make_train_step

    with pytest.raises(ValueError):
        make_train_step(Config(grad_sync="quant"), None)
    with pytest.raises(ValueError):
        make_train_step(Config(grad_sync="quant"), make_mesh({"tp": N}))
    with pytest.raises(ValueError):
        make_train_step(Config(grad_sync="bogus"), make_mesh({"dp": N}))
