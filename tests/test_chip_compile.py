"""Main-path programs compiled for a described v5e chip (no chip needed).

The TPU compiler is installed here and compiles for a topology that is
described, not attached: these tests catch what interpret mode cannot (an
unlowerable Pallas block, a kernel over its VMEM budget, a collective the
partitioner refuses) at real widths, at no chip time.  Nothing runs, so
they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may hold libtpu, and a module that decides at import whether
its tests exist gives pytest-xdist workers different collections.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ompi_tpu.ops.attention import flash_attention_partials, flash_mha


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_mha_fwd_bwd_flagship_shape(one_chip):
    shape = (1, 2048, 16, 128)            # flagship_config per sequence

    def loss(q, k, v):
        o = flash_mha(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    args = [_sds(shape, jnp.bfloat16, one_chip)] * 3
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    # forward + dq + dk/dv kernels, all compiled to Mosaic
    assert hlo.count("tpu_custom_call") >= 3
    # each under its own name, which the profiler's op events carry
    # (a transform may wrap it: transpose_jvp_ompi_flash_bwd_dq__)
    ops = re.findall(r"%(\S+) = .*tpu_custom_call", hlo)
    stable = ("ompi_flash_partials", "ompi_flash_bwd_dkv",
              "ompi_flash_bwd_dq")
    assert all(any(k in op for k in stable) for op in ops), ops
    assert all(any(k in op for op in ops) for k in stable), ops


def test_flash_partials_ring_shard(one_chip):
    # one ring hop of the flagship over 4 sequence shards: (b*h, s/4, d)
    shape = (16, 512, 128)
    args = [_sds(shape, jnp.bfloat16, one_chip)] * 3
    fn = jax.jit(lambda q, k, v: flash_attention_partials(
        q, k, v, causal=True, q_offset=512, kv_offset=0, interpret=False))
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "%ompi_flash_partials" in hlo


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
def test_paged_attention_serve_shape(topo, tp, monkeypatch):
    """``_j_paged_attn`` at the serve cell's shapes (64 slots x 128 pages
    of 16 positions, 16 heads x 128 split over tp, bf16 pools): the
    ``ompi_paged_attn`` kernel compiled to Mosaic, at tp 2 once per chip
    under the shard_map, with no gather and no pool-sized temporary.
    The backend here is the CPU, so the test turns the interpreter off."""
    from ompi_tpu.ops import paged_attention
    from ompi_tpu.serving.engine import _j_paged_attn

    monkeypatch.setattr(paged_attention, "_default_interpret",
                        lambda: False)

    if tp == 1:
        row = rep = SingleDeviceSharding(topo.devices[0])
        comm = {}
    else:
        mesh = Mesh(np.asarray(topo.devices[:tp]), ("tp",))
        row, rep = NamedSharding(mesh, P("tp")), NamedSharding(mesh, P())
        comm = {"mesh": mesh, "axis": "tp"}
    heads, slots, pmax, page = 16 // tp, 64, 128, 16
    pool = _sds((tp, slots * pmax + 1, page, heads, 128), jnp.bfloat16, row)
    compiled = _j_paged_attn.lower(
        _sds((tp, slots, heads, 128), jnp.bfloat16, row), pool, pool,
        _sds((slots, pmax), jnp.int32, rep), _sds((slots,), jnp.int32, rep),
        **comm).compile()
    hlo = compiled.as_text()
    assert re.search(r"%\S*ompi_paged_attn\S* = .*tpu_custom_call", hlo)
    assert " gather(" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 24)


def test_device_comm_collectives_four_chips(topo):
    from ompi_tpu.op import SUM
    from ompi_tpu.parallel import DeviceComm

    mesh = Mesh(np.asarray(topo.devices), ("x",))
    dc = DeviceComm(mesh, "x")
    sh = NamedSharding(mesh, P("x"))
    x = _sds((4, 4, 1 << 16), jnp.float32, sh)     # (R, R, b), 1 row/chip

    def prog(v):
        y = dc.allreduce(v, SUM)
        return dc.alltoall(y)

    compiled = jax.jit(prog).lower(x).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo and "all-to-all" in hlo
    assert compiled.memory_analysis().argument_size_in_bytes == (
        4 * (1 << 16) * 4)                          # one row per chip


def test_dp_tp_step_moves_what_the_yardstick_counts(topo):
    """The dp2 x tp2 train step compiled for the described 2x2 host: the
    program's own view of its collectives (``step.comm_graph``, read from
    the partitioned HLO) against ``benchmark/comm_bytes.py``.  Megatron's
    tensor-parallel all-reduces are exactly the yardstick's; the dp
    gradient all-reduce is the yardstick's plus one more copy of the
    embedding shard's gradient (XLA reduces the tied embedding's lookup
    and head contributions apart).  The rest of the tp traffic moves
    blocks of the fused QKV weight, which the yardstick does not count:
    its column halves are not whole heads, so the step projects from
    head-aligned blocks of it.  No activation is reshuffled: no
    all-to-all, every permute has a weight's shape, and those bytes stay
    put when the rows or the sequence double."""
    import optax

    from benchmark import comm_bytes
    from ompi_tpu.models.transformer import (Config, init_params,
                                             make_train_step, param_specs)

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "tp"))
    cfg = Config(vocab=1024, d_model=512, n_layers=2, n_heads=4,
                 head_dim=128, d_ff=1024, seq=256, attn="flash",
                 remat="dots")
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    params = jax.tree.map(
        lambda x, s: _sds(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())

    def comm_graph(cfg, rows):
        init_opt, step = make_train_step(cfg, mesh)
        opt = tuple(
            optax.ScaleByAdamState(count=_sds((), o.count.dtype, rep),
                                   mu=params, nu=params)
            if isinstance(o, optax.ScaleByAdamState) else o
            for o in jax.eval_shape(init_opt, shapes))
        tokens = _sds((rows, cfg.seq + 1), jnp.int32,
                      NamedSharding(mesh, P("dp", None)))
        return step.comm_graph(params, opt, tokens)

    g = comm_graph(cfg, 4)
    assert not [i for i in g.check(mesh) if i.severity == "error"]
    assert all(r.bounded and r.trips == 1 for r in g.records)

    def payload(axes, ops, g=g):
        return sum(r.nbytes for r in g.records
                   if r.axes == axes and r.op in ops and not r.control)

    want_tp = comm_bytes.tp_payload(cfg.d_model, cfg.n_layers, rows=2,
                                    seq=cfg.seq)
    assert payload(("tp",), ("psum", "pmax")) == want_tp
    embed_shard = cfg.vocab // 2 * cfg.d_model * 2
    want_dp = comm_bytes.dp_payload(cfg.d_model, cfg.n_layers, cfg.n_heads,
                                    cfg.head_dim, cfg.d_ff, cfg.vocab, tp=2)
    assert payload(("dp",), ("psum",)) == want_dp + embed_shard
    assert {r.op for r in g.records} <= {"psum", "pmax", "ppermute",
                                         "all_to_all"}
    assert {r.axes for r in g.records} == {("tp",), ("dp",)}
    wire = g.wire_by_axes(mesh)
    assert wire[("dp",)] == want_dp + embed_shard
    assert wire[("tp",)] >= want_tp

    tp_records = [r for r in g.records if r.axes == ("tp",)]
    assert not [r for r in tp_records if r.op == "all_to_all"]
    permutes = [r for r in tp_records if r.op == "ppermute"]
    assert permutes
    assert all(r.shape[0] == cfg.d_model for r in permutes), permutes

    def weight_moves(g):
        return sum(r.nbytes for r in g.records if r.axes == ("tp",)
                   and r.op not in ("psum", "pmax") and not r.control)

    moved = weight_moves(g)
    assert moved > 0
    twice_seq = dataclasses.replace(cfg, seq=2 * cfg.seq)
    assert weight_moves(comm_graph(cfg, 8)) == moved
    assert weight_moves(comm_graph(twice_seq, 4)) == moved
