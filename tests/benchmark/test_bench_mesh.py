"""The dp x tp training cell on the CPU at small sizes: the program's sharded
step follows the float32 reference, the reference laid out over four
devices is the one-device reference, a run through the harness passes while
the control and each planted fault fail, the yardstick of the step's
communication counts by hand, and the per-layer readers read synthetic
traces as they are defined and split a trace recorded on four chips into
its tp and dp collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import comm_bytes, mesh_trace
from benchmark import run as br
from benchmark.drivers import train as one_chip
from benchmark.references import olmo as ref
from benchmark.references import olmo_mesh
from ompi_tpu.models.transformer import make_train_step, shard_params
from ompi_tpu.parallel.mesh import make_mesh

SEED = 2**32 + 12345          # larger than 32 signed bits
CELL = "olmo-1b.dp2tp2-train"


def _tiny_mesh_cell():
    cell = br.load_cell(CELL)
    cell["config_data"].update(
        d_model=64, n_heads=4, head_dim=16, n_layers=2, mlp_hidden_size=128,
        embedding_size=512, vocab_size=500, eos_token_id=499)
    cell.update(seq=64, pool=6, batch=4)
    cell["doc_len"].update(median=20, max=200)
    return cell


def _batches(cell, n=3):
    cfg = cell["config_data"]
    key = jax.random.key(br.seed32(SEED))
    return key, one_chip.make_batches(cell, cfg, SEED,
                                      jax.random.fold_in(key, 1))[:n]


def test_sharded_step_follows_the_reference():
    """Three dp2 x tp2 steps (flash attention per block, 2 heads a chip)
    against the one-device float32 reference from the same params and
    rows.  The program runs bfloat16 matmuls over float32 master params;
    at this size it reads about 2e-4 on the losses and 1.5e-3 on the norm
    gaps, and the reference in float8 in its place reads 2e-3, 0.15 and
    0.06 (test_mesh_control_float8_fails): each tolerance lies between."""
    cell = _tiny_mesh_cell()
    cfg = cell["config_data"]
    key, batches = _batches(cell)
    mesh = make_mesh(dict(cell["mesh"]), jax.devices()[:4])
    pcfg = one_chip.program_config(cfg, cell)
    init = ref.make_init(cfg)
    params = shard_params(init(key), mesh, pcfg)
    init_opt, step = make_train_step(pcfg, mesh)
    opt = init_opt(params)
    data = NamedSharding(mesh, P("dp", None))
    losses = []
    for i, b in enumerate(batches):
        params, opt, loss = step(params, opt, jax.device_put(b, data))
        losses.append(float(loss))
        if i == 0:
            g1 = ref.leaf_norms(opt[0].mu) / (1 - ref.ADAM["b1"])
    change = ref.change_norms(init, key, params)
    r = ref.run_reference(cfg, key, batches)
    # bfloat16 rounding of activations averages out over 256 positions
    np.testing.assert_allclose(losses, r["losses"], rtol=1e-3)
    # per-leaf norms: bfloat16 matmuls, gradients summed over dp in bf16
    assert ref.gap(g1, r["grad_norms"]) < 0.01
    assert ref.gap(change, r["change_norms"]) < 0.01
    # the params and the optimizer state come back as they went in: the
    # step compiled once for all three calls
    assert step.jitted._cache_size() == 1


def test_mesh_reference_is_the_one_device_reference():
    """The same float32 steps laid out over four devices; only the order
    of float32 sums differs."""
    cell = _tiny_mesh_cell()
    cfg = cell["config_data"]
    key, batches = _batches(cell)
    one = ref.run_reference(cfg, key, batches)
    four = olmo_mesh.run_reference(cfg, key, batches, jax.devices()[:4])
    np.testing.assert_allclose(four["losses"], one["losses"], rtol=1e-5)
    assert ref.gap(four["grad_norms"], one["grad_norms"]) < 1e-4
    assert ref.gap(four["change_norms"], one["change_norms"]) < 1e-4


@pytest.mark.parametrize("impl,want", [
    ("program", True), ("fault:unchanged", False),
    ("fault:half_batch", False)])
def test_mesh_train_correct(impl, want):
    out = br.run_cell(_tiny_mesh_cell(), SEED, 0.05, False,
                      jax.devices()[:4], impl=impl)
    assert out["correct"] is want, out["compared"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 4


def test_mesh_control_float8_fails():
    cell = _tiny_mesh_cell()
    cfg = cell["config_data"]
    key, batches = _batches(cell)
    devs = jax.devices()[:4]
    base = olmo_mesh.run_reference(cfg, key, batches, devs)
    ctl = olmo_mesh.run_reference(cfg, key, batches, devs,
                                  mm_dtype=jnp.float8_e4m3fn)
    checks = one_chip.compare(ctl, base, cell["limits"])
    assert any(c["value"] > c["limit"] for c in checks), checks


def test_yardstick_by_hand():
    cfg = br.load_cell(CELL)["config_data"]
    got = comm_bytes.step_bus_bytes(cfg, rows_per_replica=4, seq=2048,
                                    dp=2, tp=2)
    act = 4 * 2048 * 2048 * 2                      # (4, 2048, 2048) bf16
    # over two chips an all-reduce's bus bytes are its buffer
    assert got["tp"] == (4 * 16 + 2) * act + 4 * 2048 * (2 + 4 + 4)
    per_layer = 2048 * 6144 + 2048 * 2048 + 3 * 2048 * 8192
    per_chip = (16 * per_layer + 50304 * 2048) // 2 + 33 * 2048
    assert per_chip == 588_449_792
    assert got["dp"] == per_chip * 2
    # one chip alone moves nothing
    assert comm_bytes.step_bus_bytes(cfg, 8, 2048, 1, 1) == {"tp": 0.0,
                                                             "dp": 0.0}


# -- readers on synthetic traces --------------------------------------------

TP_AR = ("%all-reduce.3 = bf16[4,8]{1,0} all-reduce(bf16[4,8]{1,0} %f), "
         "channel_id=1, replica_groups=[2,2]<=[4], use_global_device_ids=true")
DP_AR = ("%all-reduce.9 = bf16[64]{0} all-reduce(bf16[64]{0} %g), "
         "channel_id=2, replica_groups=[2,2]<=[2,2]T(1,0), "
         "use_global_device_ids=true")
DP_AR_LIST = ("%all-reduce.10 = f32[] all-reduce(f32[] %l), channel_id=3, "
              "replica_groups={{0,2},{1,3}}, use_global_device_ids=true")
TP_PERM = ("%collective-permute-start.1 = (bf16[4]{0}, bf16[4]{0}) "
           "collective-permute-start(bf16[4]{0} %q), channel_id=4, "
           "source_target_pairs={{1,0},{3,2}}")
TP_A2A = "%all-to-all.2 = bf16[1,2,4]{2,1,0} all-to-all(bf16[1,2,4] %c)"
TP_DONE = ("%collective-permute-done.1 = bf16[4]{0} collective-permute-done("
           "(bf16[4]{0}, bf16[4]{0}) %collective-permute-start.1)")
FUSION = "%fusion.7 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %x), kind=kLoop"
RECS = {"mesh_axes": ["dp", "tp"], "mesh_shape": [2, 2],
        "coll_axes": {"all-to-all.2": "tp",
                      "collective-permute-start.1": "tp"}}


def test_op_axes_from_groups_and_names():
    assert mesh_trace.op_axes(TP_AR, RECS) == "tp"
    assert mesh_trace.op_axes(DP_AR, RECS) == "dp"
    assert mesh_trace.op_axes(DP_AR_LIST, RECS) == "dp"
    assert mesh_trace.op_axes(TP_PERM, RECS) == "tp"
    # no groups in the text: the name the driver read from the compile
    assert mesh_trace.op_axes(TP_A2A, RECS) == "tp"
    assert mesh_trace.op_axes(TP_A2A, dict(RECS, coll_axes={})) is None
    # an async -done is its start's
    assert mesh_trace.op_axes(TP_DONE, RECS) == "tp"
    whole = TP_AR.replace("[2,2]<=[4]", "{{0,1,2,3}}")
    assert mesh_trace.op_axes(whole, RECS) == "dp+tp"
    assert mesh_trace.op_axes(TP_AR, {}) is None


def _trace(devices):
    return {"devices": devices, "modules": {},
            "spans": [(100, 1100, "bench.window")]}


def test_exposed_share_counts_a_collective_alone():
    # device 0: matmul 100-500; tp all-reduce 400-700 (100 under the
    # matmul, 200 alone); dp all-reduce 900-1000 alone; 0-50 is outside
    # the window.  Device 1: the all-reduce runs under a fusion throughout
    d0 = [(0, 50, TP_AR), (100, 500, FUSION), (400, 700, TP_AR),
          (900, 1000, DP_AR)]
    d1 = [(100, 600, FUSION), (200, 300, TP_AR)]
    share = mesh_trace.exposed_share(_trace({"d0": d0, "d1": d1, "d2": []}))
    assert share == pytest.approx((300 / 1000 + 0.0) / 2)
    assert mesh_trace.exposed_share(_trace({})) is None


def test_ici_shares_split_tp_from_dp():
    d0 = [(100, 300, TP_AR), (300, 400, TP_PERM), (500, 600, DP_AR),
          (600, 700, FUSION), (1050, 1200, DP_AR)]      # the last half out
    d1 = [(100, 200, TP_AR), (200, 300, TP_A2A), (500, 550, DP_AR)]
    tr = _trace({"d0": d0, "d1": d1})
    assert mesh_trace.axis_time(tr, RECS, "tp") == pytest.approx(
        (300 + 200) / 2 / 1e9)
    assert mesh_trace.axis_time(tr, RECS, "dp") == pytest.approx(
        (100 + 50 + 50) / 2 / 1e9)
    run = {"trace": tr, "records": dict(RECS, steps=2,
                                        bus_bytes={"tp": 10.0, "dp": 4.0})}
    peak = 1e9
    assert mesh_trace.ici_share(run, "tp", peak) == pytest.approx(
        100 * 10.0 * 2 / 250e-9 / peak)
    assert mesh_trace.ici_share(run, "dp", peak) == pytest.approx(
        100 * 4.0 * 2 / 100e-9 / peak)
    run["records"].pop("bus_bytes")
    assert mesh_trace.ici_share(run, "tp", peak) is None


def _recorded():
    """A dp2 x tp2 step recorded on four v5e chips (the file says how)."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "mesh_small.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    tr = {k: {d: [tuple(e) for e in evs]
              for d, evs in doc["trace"][k].items()}
          for k in ("devices", "modules")}
    tr["spans"] = [tuple(s) for s in doc["trace"]["spans"]]
    return doc, tr


def test_recorded_trace_splits_tp_from_dp():
    from collections import Counter

    from benchmark import trace_reduce

    doc, tr = _recorded()
    recs = dict(RECS, coll_axes=doc["coll_axes"])
    bare = dict(RECS, coll_axes={})
    assert sorted(tr["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    for evs in tr["devices"].values():
        colls = [n for _, _, n in evs if trace_reduce.is_collective(n)]
        # two steps of one layer: per step 16 tp ops (Megatron's
        # all-reduces and the fused QKV's permutes and all-to-alls, the
        # permutes' -done halves among them) and the dp gradient sums
        assert Counter(mesh_trace.op_axes(n, recs) for n in colls) == {
            "tp": 40, "dp": 4}
        # by the text's groups alone, only the -done halves are unknown
        unknown = [n for n in colls if mesh_trace.op_axes(n, bare) is None]
        assert len(unknown) == 8 and all("-done(" in n for n in unknown)
        # where both are known, the groups and the compiled step agree
        for n in colls:
            instr = n.split(" = ", 1)[0].lstrip("%")
            if mesh_trace.groups_of(n) and instr in doc["coll_axes"]:
                assert mesh_trace.op_axes(n, bare) == doc["coll_axes"][instr]
    tp = mesh_trace.axis_time(tr, recs, "tp")
    dp = mesh_trace.axis_time(tr, recs, "dp")
    assert tp > dp > 0
    share = mesh_trace.exposed_share(tr)
    busy = 1 - trace_reduce.reduce(tr)["idle_share"]
    assert 0 < share < busy
    assert trace_reduce.op_time(tr, trace_reduce.is_collective) >= (
        tp + dp) * (1 - 1e-9)
