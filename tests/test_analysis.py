"""Static communication verifier + comm-lint (ompi_tpu/analysis).

Acceptance pins (ISSUE 11): jaxpr extraction sees every explicitly
dispatched collective (ring's scan-multiplied ppermutes, ulysses'
alltoall pair, the grad-sync psums) with axis/dtype/shape/trip
metadata; the SPMD checks catch the MPI-Checker violation catalog
(non-bijective or out-of-range ppermute, cond-divergent sequences,
unknown axes, host callbacks in device paths, data-dependent while
bounds, hier splits that reuse an inner axis); the static wire models
use the same 2(r-1)/r-family factors as ``perf/model._FACTOR``; and
``verify()`` proves static == runtime wire bytes **byte-for-byte**
for ring attention, ulysses, perleaf grad sync, a small train step
and a compiled reshard plan on the 8-device CPU mesh.  The lint half:
each rule CL001-CL006 fires on a minimal bad program, stays quiet on
the repaired one, honours justified waivers (and only justified
ones), and the shipped tree itself is clean.  The rules half: the
shared DEVICE_RULES validator accepts the shipped file, rejects
duplicate rows naming both lines, and the coll/xla loader delegates
to it.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

pytestmark = pytest.mark.analysis

from ompi_tpu import traffic  # noqa: E402
from ompi_tpu.analysis import commgraph, lint, rules  # noqa: E402
from ompi_tpu.analysis.commgraph import (  # noqa: E402
    CollRecord,
    CommGraph,
    extract,
    from_reshard_plan,
    verify,
)
from ompi_tpu.parallel import make_mesh, overlap  # noqa: E402
from ompi_tpu.parallel.reshard import Resharder, compile_plan  # noqa: E402
from ompi_tpu.parallel.ring import ring_attention  # noqa: E402
from ompi_tpu.parallel.ulysses import ulysses_attention  # noqa: E402


@pytest.fixture
def sp8():
    return make_mesh({"sp": 8})


@pytest.fixture
def dp8():
    return make_mesh({"dp": 8})


def _qkv(heads=8, dtype=jnp.float32):
    rng = np.random.default_rng(0)
    shape = (1, 64, heads, 8)             # (batch, seq, heads, head_dim)
    mk = lambda: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    return mk(), mk(), mk()


# -- extraction --------------------------------------------------------------

class TestExtract:
    def test_ring_attention_scan_trips(self, sp8):
        q, k, v = _qkv()
        g = extract(lambda a, b, c: ring_attention(a, b, c, sp8, axis="sp"),
                    q, k, v, source="ring")
        pp = [r for r in g.records if r.op == "ppermute"]
        # the fori_loop lowers to a scan of length n: K and V hop once
        # per trip, so trips carries the ring length
        assert pp and all(r.trips == 8 for r in pp)
        assert all(r.axes == ("sp",) for r in pp)
        assert all(len(r.perm) == 8 for r in pp)
        # n hops of the 1/n shard == one full pass of the global K+V
        assert g.ppermute_bytes() == k.nbytes + v.nbytes

    def test_ulysses_alltoall_records(self, sp8):
        q, k, v = _qkv()
        g = extract(lambda a, b, c: ulysses_attention(a, b, c, sp8,
                                                      axis="sp"),
                    q, k, v, source="ulysses")
        a2a = [r for r in g.records if r.op == "all_to_all"]
        assert len(a2a) == 4              # q/k/v seq->heads + out heads->seq
        assert g.all_to_all_bytes() == \
            (2 * q.nbytes + k.nbytes + v.nbytes) // 8

    def test_scalar_psum_is_control(self, dp8):
        def prog(x):
            def local(v):
                return lax.psum(v.sum(), "dp"), lax.psum(v, "dp")
            return jax.shard_map(local, mesh=dp8, in_specs=(P("dp"),),
                                 out_specs=(P(), P()))(x)

        x = jnp.ones((8, 4), jnp.float32)
        g = extract(prog, x)
        psums = [r for r in g.records if r.op == "psum"]
        assert any(r.control for r in psums)
        assert any(not r.control for r in psums)
        # only the payload psum prices: 2(n-1)/n x the 4-float shard
        assert g.psum_ring_bytes(dp8) == 2 * 7 * 16 // 8

    def test_graph_bookkeeping(self, dp8):
        def prog(x):
            return jax.shard_map(lambda v: lax.psum(v, "dp"), mesh=dp8,
                                 in_specs=(P("dp"),), out_specs=P())(x)

        g = extract(prog, jnp.ones((8,), jnp.float32), source="bk")
        assert g.source == "bk"
        assert g.signatures() and g.by_op().get("psum")
        assert all("shard_map" in r.path for r in g.records)


# -- SPMD well-formedness checks ---------------------------------------------

def _rec(**kw):
    base = dict(op="psum", axes=("x",), dtype="float32", shape=(4,),
                nbytes=16)
    base.update(kw)
    return CollRecord(**base)


class TestChecks:
    def test_clean_program_has_no_issues(self, sp8):
        q, k, v = _qkv()
        g = extract(lambda a, b, c: ring_attention(a, b, c, sp8, axis="sp"),
                    q, k, v)
        assert g.check(sp8) == []

    def test_non_bijective_ppermute(self):
        g = CommGraph(records=[_rec(op="ppermute",
                                    perm=((0, 1), (1, 1), (2, 0)))])
        issues = g.check({"x": 8})
        assert any(i.kind == "bijection" and "bijection" in i.msg
                   for i in issues)

    def test_ppermute_outside_axis_domain(self):
        g = CommGraph(records=[_rec(op="ppermute", perm=((0, 9),))])
        issues = g.check({"x": 8})
        assert any(i.kind == "bijection" and "domain" in i.msg
                   for i in issues)

    def test_unknown_axis(self):
        g = CommGraph(records=[_rec(axes=("nope",))])
        issues = g.check({"x": 8})
        assert any(i.kind == "unknown-axis" for i in issues)

    def test_divergent_cond_branches(self, dp8):
        ring = [(i, (i + 1) % 8) for i in range(8)]

        def prog(x):
            def local(v):
                return lax.cond(v[0] > 0,
                                lambda u: lax.psum(u, "dp"),
                                lambda u: lax.ppermute(u, "dp", ring),
                                v)
            return jax.shard_map(local, mesh=dp8, in_specs=(P("dp"),),
                                 out_specs=P("dp"), check_vma=False)(x)

        g = extract(prog, jnp.ones((8,), jnp.float32))
        assert g.divergent_conds
        assert any(i.kind == "mismatch" for i in g.check(dp8))

    def test_identical_cond_branches_ok(self, dp8):
        def prog(x):
            def local(v):
                return lax.cond(v[0] > 0,
                                lambda u: lax.psum(u, "dp"),
                                lambda u: lax.psum(u * 2.0, "dp"),
                                v)
            return jax.shard_map(local, mesh=dp8, in_specs=(P("dp"),),
                                 out_specs=P())(x)

        g = extract(prog, jnp.ones((8,), jnp.float32))
        assert not g.divergent_conds
        assert not any(i.kind == "mismatch" for i in g.check(dp8))

    def test_host_callback_flagged(self):
        def prog(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        g = extract(prog, jnp.ones((4,), jnp.float32))
        assert g.host_transfers
        assert any(i.kind == "host-transfer" for i in g.check())

    def test_while_marks_unbounded(self, dp8):
        def prog(x):
            def local(v):
                def body(c):
                    i, a = c
                    return i + 1, lax.psum(a, "dp") * 0.4
                def cond(c):
                    return jnp.logical_and(c[0] < 64, c[1].sum() > 1e-3)
                return lax.while_loop(cond, body, (0, v))[1]
            return jax.shard_map(local, mesh=dp8, in_specs=(P("dp"),),
                                 out_specs=P("dp"), check_vma=False)(x)

        g = extract(prog, jnp.ones((8,), jnp.float32))
        psums = [r for r in g.records if r.op == "psum"]
        assert psums and not psums[0].bounded
        unb = [i for i in g.check(dp8) if i.kind == "unbounded"]
        assert unb and all(i.severity == "warn" for i in unb)

    def test_hier_outer_reusing_inner_axis(self):
        g = CommGraph(records=[
            _rec(op="reduce_scatter", axes=("inner",)),
            _rec(op="psum", axes=("inner",)),
            _rec(op="all_gather", axes=("inner",)),
        ])
        issues = g.check({"inner": 4, "outer": 2})
        assert any(i.kind == "hier-cover" and i.severity == "error"
                   for i in issues)

    def test_hier_proper_split_clean(self):
        g = CommGraph(records=[
            _rec(op="reduce_scatter", axes=("inner",)),
            _rec(op="psum", axes=("outer",)),
            _rec(op="all_gather", axes=("inner",)),
        ])
        assert not [i for i in g.check({"inner": 4, "outer": 2})
                    if i.severity == "error"]

    def test_cross_program_match(self):
        a = CommGraph(records=[_rec(), _rec(op="all_gather")])
        b = CommGraph(records=[_rec(), _rec(op="reduce_scatter")])
        assert any(i.kind == "mismatch" for i in a.match(b))
        c = CommGraph(records=[_rec()])
        assert any("count differs" in i.msg for i in a.match(c))
        assert a.match(a) == []


# -- wire models vs perf/model factors ---------------------------------------

class TestWireModels:
    def test_factors_agree_with_perf_model(self):
        # ompi_tpu.perf re-exports a CostModel instance named `model`,
        # shadowing the submodule — go through sys.modules
        import importlib
        perf_model = importlib.import_module("ompi_tpu.perf.model")
        n, payload = 8, 4096
        g = CommGraph(records=[
            _rec(op="psum", shape=(1024,), nbytes=payload),
            _rec(op="all_gather", shape=(1024,), nbytes=payload),
            _rec(op="reduce_scatter", shape=(1024,), nbytes=payload),
        ])
        sizes = {"x": n}
        assert g.psum_ring_bytes(sizes) == \
            int(perf_model._FACTOR["allreduce"](n) * payload)
        # allgather's (r-1)/r prices the gathered buffer (n x shard)
        assert g.gather_scatter_bytes(sizes) == \
            int(perf_model._FACTOR["allgather"](n) * payload * n) + \
            int(perf_model._FACTOR["reduce_scatter"](n) * payload)

    def test_single_device_axis_is_free(self):
        g = CommGraph(records=[_rec()])
        assert g.psum_ring_bytes({"x": 1}) == 0

    def test_reshard_plan_lift(self, sp8):
        mesh = make_mesh({"x": 8})
        plan = compile_plan((64, 8), jnp.float32, P("x", None),
                            P(None, "x"), mesh)
        g = from_reshard_plan(plan)
        assert g.reshard_bytes() == plan.wire_bytes
        assert all(r.path.startswith("reshard-plan") for r in g.records)
        assert g.check(mesh) == []


# -- verify(): static == runtime, byte for byte ------------------------------

@pytest.fixture
def clean_traffic():
    traffic.reset()
    yield
    traffic.reset()
    traffic.disable()


class TestVerifyByteForByte:
    def test_ring_attention(self, sp8, clean_traffic):
        q, k, v = _qkv()
        rep = verify(lambda a, b, c: ring_attention(a, b, c, sp8,
                                                    axis="sp"),
                     (q, k, v), sp8,
                     coll_map={"ring_attention": "ppermute"},
                     source="ring")
        assert rep.ok, rep.summary()
        row = next(r for r in rep.rows if r["coll"] == "ring_attention")
        assert row["static"] == row["runtime"] == k.nbytes + v.nbytes

    def test_ulysses(self, sp8, clean_traffic):
        q, k, v = _qkv()
        rep = verify(lambda a, b, c: ulysses_attention(a, b, c, sp8,
                                                       axis="sp"),
                     (q, k, v), sp8,
                     coll_map={"ulysses": "all_to_all"}, source="ulysses")
        assert rep.ok, rep.summary()
        row = next(r for r in rep.rows if r["coll"] == "ulysses")
        assert row["static"] == row["runtime"] == \
            (2 * q.nbytes + k.nbytes + v.nbytes) // 8

    def test_perleaf_grad_sync(self, dp8, clean_traffic):
        params = {"w": jnp.ones((16, 16), jnp.float32),
                  "b": jnp.zeros((16,), jnp.float32)}

        def local_loss(p, t):
            return jnp.mean((t @ p["w"] + p["b"]) ** 2)

        vg = overlap.make_grad_sync("perleaf", dp8, local_loss)
        batch = jnp.ones((8, 16), jnp.float32)
        rep = verify(vg, (params, batch), dp8,
                     coll_map={"grad_sync": "psum_ring"}, source="perleaf")
        assert rep.ok, rep.summary()
        row = next(r for r in rep.rows if r["coll"] == "grad_sync")
        flat = sum(x.nbytes for x in jax.tree.leaves(params))
        assert row["static"] == row["runtime"] == 2 * 7 * flat // 8

    def test_small_train_step(self, dp8, clean_traffic):
        from ompi_tpu.models.transformer import (Config, init_params,
                                                 loss_fn, make_train_step)
        cfg = Config(vocab=64, d_model=32, n_layers=2, n_heads=4,
                     head_dim=8, d_ff=64, seq=32, dtype=jnp.float32,
                     attn="dense", grad_sync="perleaf")
        params = init_params(jax.random.key(0), cfg)
        init_opt, step = make_train_step(cfg, dp8)
        opt_state = init_opt(params)
        tokens = jnp.zeros((8, cfg.seq + 1), jnp.int32)
        # the jitted step never fires the eager note models (tracers
        # inside), so the runtime side replays the equivalent eager
        # grad-sync path while the static side reads the step program
        vg = overlap.make_grad_sync(
            "perleaf", dp8, lambda p, t: loss_fn(p, t, cfg, None))
        rep = verify(step, (params, opt_state, tokens), dp8,
                     coll_map={"grad_sync": "psum_ring"},
                     runner=lambda: jax.block_until_ready(
                         vg(params, tokens)),
                     source="train-step")
        assert rep.ok, rep.summary()
        row = next(r for r in rep.rows if r["coll"] == "grad_sync")
        flat = sum(x.nbytes for x in jax.tree.leaves(params))
        assert row["static"] == row["runtime"] == 2 * 7 * flat // 8

    def test_reshard_plan(self, clean_traffic):
        mesh = make_mesh({"x": 8})
        plan = compile_plan((64, 8), jnp.float32, P("x", None),
                            P(None, "x"), mesh)
        g = from_reshard_plan(plan)
        rs = Resharder(mesh)
        x = jax.device_put(
            np.arange(64 * 8, dtype=np.float32).reshape(64, 8),
            NamedSharding(mesh, P("x", None)))
        rep = verify(lambda: None, (), mesh, graph=g,
                     coll_map={"reshard": "reshard"},
                     runner=lambda: jax.block_until_ready(
                         rs.run(x, P(None, "x"))))
        assert rep.ok, rep.summary()
        row = next(r for r in rep.rows if r["coll"] == "reshard")
        assert row["static"] == row["runtime"] == plan.wire_bytes > 0

    def test_report_shape(self, sp8, clean_traffic):
        q, k, v = _qkv()
        rep = verify(lambda a, b, c: ring_attention(a, b, c, sp8,
                                                    axis="sp"),
                     (q, k, v), sp8,
                     coll_map={"ring_attention": "ppermute"})
        j = rep.to_json()
        assert set(j) == {"source", "ok", "n_records", "issues", "rows",
                          "host_transfers"}
        assert rep.summary().startswith("commgraph:")
        assert not traffic.enabled   # prior disabled state restored


# -- comm-lint ---------------------------------------------------------------

_SPAN_BAD = '''
import time
from ompi_tpu import trace

def build_it(build, key):
    t0 = time.perf_counter()
    fn = build()
    trace.record_span("build", "compile", t0, time.perf_counter())
    return fn
'''

_SPAN_GOOD = '''
import time
from ompi_tpu import trace

def build_it(build, key):
    t0 = time.perf_counter()
    try:
        fn = build()
    except BaseException:
        trace.record_span("build", "compile", t0, time.perf_counter(),
                          args={"status": "error"})
        raise
    trace.record_span("build", "compile", t0, time.perf_counter())
    return fn
'''


class TestLint:
    def _codes(self, findings, waived=False):
        return [f.rule for f in findings if f.waived == waived]

    def test_cl001_raw_collective(self):
        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'dp')\n")
        out = lint.lint_sources({"ompi_tpu/newmod.py": src})
        assert self._codes(out) == ["CL001"]

    def test_cl001_engine_layer_exempt(self):
        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'dp')\n")
        out = lint.lint_sources({"ompi_tpu/coll/xla.py": src})
        assert out == []

    def test_cl002_unprotected_span(self):
        out = lint.lint_sources({"ompi_tpu/newmod.py": _SPAN_BAD})
        assert self._codes(out) == ["CL002"]

    def test_cl002_protected_span_clean(self):
        out = lint.lint_sources({"ompi_tpu/newmod.py": _SPAN_GOOD})
        assert out == []

    def test_cl003_unlisted_pvar(self):
        spc = 'COUNTERS = [("listed_total", "d")]\n'
        plane = 'PVARS = ("listed_total", "ghost_total")\n'
        out = lint.lint_sources({"ompi_tpu/spc.py": spc,
                                 "ompi_tpu/plane.py": plane})
        assert self._codes(out) == ["CL003"]
        assert "ghost_total" in out[0].msg

    def test_cl004_gate_not_first(self):
        src = ("from ompi_tpu import traffic\n"
               "def f(x):\n"
               "    if x > 0 and traffic.enabled:\n"
               "        pass\n")
        out = lint.lint_sources({"ompi_tpu/newmod.py": src})
        assert self._codes(out) == ["CL004"]

    def test_cl004_registry_read_at_call_site(self):
        src = ("from ompi_tpu.core import var as _var\n"
               "def f():\n"
               "    return _var.get('perf_enabled')\n")
        out = lint.lint_sources({"ompi_tpu/newmod.py": src})
        assert self._codes(out) == ["CL004"]
        # the plane's own module may read its var (it defines .enabled)
        out = lint.lint_sources({"ompi_tpu/perf/__init__.py": src})
        assert out == []

    def test_cl005_reason_grammar(self):
        bad = "def f(audit):\n    audit(reason='because I said so')\n"
        ok = "def f(audit):\n    audit(reason='rule:allreduce@dcn')\n"
        assert self._codes(lint.lint_sources(
            {"ompi_tpu/m.py": bad})) == ["CL005"]
        assert lint.lint_sources({"ompi_tpu/m.py": ok}) == []

    def test_cl006_epoch_discipline(self):
        bad = "def f(win, x):\n    win.put(x, 1)\n"
        ok = ("def f(win, x):\n"
              "    win.fence()\n"
              "    win.put(x, 1)\n"
              "    win.fence()\n")
        assert self._codes(lint.lint_sources(
            {"ompi_tpu/m.py": bad})) == ["CL006"]
        assert lint.lint_sources({"ompi_tpu/m.py": ok}) == []

    def test_waiver_with_justification(self):
        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'dp')  "
               "# comm-lint: disable=CL001 measured eager reference\n")
        out = lint.lint_sources({"ompi_tpu/m.py": src})
        assert self._codes(out) == [] and self._codes(out, True) == \
            ["CL001"]
        assert out[0].waiver == "measured eager reference"

    def test_waiver_without_justification_stays(self):
        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'dp')  # comm-lint: disable=CL001\n")
        out = lint.lint_sources({"ompi_tpu/m.py": src})
        assert self._codes(out) == ["CL001"]
        assert "NO justification" in out[0].msg

    def test_shipped_tree_is_clean(self):
        import os
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ompi_tpu")
        live = [f for f in lint.lint_paths([root]) if not f.waived]
        assert live == [], "\n".join(f.format() for f in live)


# -- DEVICE_RULES shared validator -------------------------------------------

class TestRulesValidator:
    def test_parse_ok(self, tmp_path):
        p = tmp_path / "r.txt"
        p.write_text("# learned from PERF_LEDGER\n"
                     "allreduce 1 0 native\n"
                     "allreduce@dcn 4 1024 hier\n")
        assert rules.parse_file(str(p)) == [
            ("allreduce", 1, 0, "native"),
            ("allreduce@dcn", 4, 1024, "hier")]

    def test_duplicate_names_both_lines(self, tmp_path):
        p = tmp_path / "r.txt"
        p.write_text("allreduce 1 0 native\n"
                     "allreduce 1 0 staged\n")
        with pytest.raises(ValueError, match=r"duplicate device rule"):
            rules.parse_file(str(p))
        try:
            rules.parse_file(str(p))
        except ValueError as e:
            msg = str(e)
        assert "line 1" in msg and ":2:" in msg
        assert "'native'" in msg and "'staged'" in msg

    def test_same_coll_different_threshold_not_duplicate(self, tmp_path):
        p = tmp_path / "r.txt"
        p.write_text("allreduce 1 0 hier\nallreduce 1 1024 hier+quant\n")
        assert len(rules.parse_file(str(p))) == 2

    def test_loader_delegates_duplicate_rejection(self, tmp_path):
        from ompi_tpu.coll.xla import _load_device_rules
        p = tmp_path / "r.txt"
        p.write_text("grad_sync@ici 1 0 native\n"
                     "grad_sync@ici 1 0 quant\n")
        with pytest.raises(ValueError, match="duplicate device rule"):
            _load_device_rules(str(p))

    def test_shipped_file_validates(self):
        import os
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "DEVICE_RULES.txt")
        rep = rules.validate_file(path)
        assert rep.ok and rep.rows and not rep.errors

    def test_cli_exit_codes(self, tmp_path, capsys):
        good = tmp_path / "good.txt"
        good.write_text("allreduce 1 0 native\n")
        bad = tmp_path / "bad.txt"
        bad.write_text("allreduce 1 0 native\nallreduce 1 0 hier\n")
        assert rules.main([str(good)]) == 0
        assert rules.main([str(bad)]) == 1


_HLO = """HloModule jit_step

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%max.2 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %m = bf16[] maximum(bf16[] %a, bf16[] %b)
}

%body.3 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %p), channel_id=9, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.1
}

ENTRY %main.4 (x: f32[4,8]) -> f32[8] {
  %x = f32[4,8]{1,0} parameter(0)
  %all-reduce.1 = (bf16[4,8]{1,0:T(8,128)(2,1)}, f32[16]{0}) all-reduce(%c, %d), channel_id=1, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add.1
  %all-reduce.2 = bf16[4]{0} all-reduce(%e), channel_id=2, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%max.2
  %all-gather.3 = f32[8,8]{1,0} all-gather(%x), channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true
  %reduce-scatter.4 = f32[2,8]{1,0} reduce-scatter(%x), channel_id=4, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true, to_apply=%add.1
  %collective-permute-start.5 = (bf16[4,8]{1,0}, bf16[4,8]{1,0}, u32[], u32[]) collective-permute-start(%c), channel_id=5, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %collective-permute-done.5 = bf16[4,8]{1,0} collective-permute-done(%collective-permute-start.5)
  %all-gather-start.6 = (f32[4]{0}, f32[8]{0}) all-gather-start(%f), channel_id=6, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true
  %all-to-all.8 = bf16[1,2,4]{2,1,0} all-to-all(%g), channel_id=8, replica_groups=[2,2]<=[4], dimensions={1}
  ROOT %while.9 = f32[8]{0} while(f32[8]{0} %h), condition=%cond.5, body=%body.3
}
"""


def test_from_compiled_reads_partitioned_hlo():
    """Groups in iota, transposed-iota and list form and a permute's
    pairs map to mesh axes; payloads are per-shard operand bytes; a
    combined all-reduce gives one record per operand; the reducer names
    pmax; -done halves are not counted; a loop body's collective is
    unbounded."""
    g = commgraph.from_compiled(_HLO, {"dp": 2, "tp": 2}, source="t")
    got = [(r.path, r.op, r.axes, r.dtype, r.shape, r.nbytes, r.bounded)
           for r in g.records]
    assert sorted(got) == sorted([
        ("body.3/all-reduce.7", "psum", ("dp", "tp"), "float32", (8,), 32,
         False),
        ("main.4/all-reduce.1", "psum", ("tp",), "bfloat16", (4, 8), 64,
         True),
        ("main.4/all-reduce.1", "psum", ("tp",), "float32", (16,), 64, True),
        ("main.4/all-reduce.2", "pmax", ("dp",), "bfloat16", (4,), 8, True),
        ("main.4/all-gather.3", "all_gather", ("dp",), "float32", (8, 8),
         128, True),
        ("main.4/reduce-scatter.4", "reduce_scatter", ("tp",), "float32",
         (2, 8), 128, True),
        ("main.4/collective-permute-start.5", "ppermute", ("tp",),
         "bfloat16", (4, 8), 64, True),
        ("main.4/all-gather-start.6", "all_gather", ("tp",), "float32", (4,),
         16, True),
        ("main.4/all-to-all.8", "all_to_all", ("tp",), "bfloat16", (1, 2, 4),
         16, True),
    ])
    perm = next(r for r in g.records if r.op == "ppermute").perm
    assert perm == ((0, 1), (1, 0))           # positions along tp
    assert [i.kind for i in g.check({"dp": 2, "tp": 2})] == ["unbounded"]
    # psum ring 2(n-1)/n, ppermute and all_to_all 1x, all_gather (n-1)x
    # the shard, reduce_scatter (n-1)/n; pmax is not priced
    assert g.wire_by_axes({"dp": 2, "tp": 2}) == {
        ("dp", "tp"): 48, ("tp",): 128 + 64 + 16 + 16 + 64, ("dp",): 128}
