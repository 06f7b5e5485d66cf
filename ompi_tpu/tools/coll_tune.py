"""Host-collective algorithm microbench → decision-table evidence.

≙ the role of OSU microbenchmarks + coll_tuned's decision tables
(coll_tuned_decision_fixed.c:55-104): run every selectable algorithm of each
tuned collective across a size sweep on threaded ranks, record µs per
(collective, algorithm, bytes), and emit the winning algorithm per size so
the fixed decision defaults in coll/tuned.py are driven by a recorded sweep
(TUNE_SWEEP.json at the repo root), not guesses.

Usage:  python -m ompi_tpu.tools.coll_tune [--ranks 4] [--iters 5]
                                           [--out TUNE_SWEEP.json]

Caveat recorded into the output: this box exposes one CPU core, so absolute
µs include scheduler noise; the *ranking* between algorithms at a size is
the signal (identical conditions per candidate).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

ALGS = {
    "allreduce": ["recursive_doubling", "ring", "segmented_ring",
                  "rabenseifner", "nonoverlapping", "allgather_reduce"],
    "bcast": ["binomial", "knomial", "pipeline", "chain",
              "scatter_allgather", "split_binary"],
    "allgather": ["recursive_doubling", "ring", "neighbor_exchange", "bruck",
                  "sparbit", "k_bruck", "direct"],
    "alltoall": ["pairwise", "bruck", "linear_sync", "linear"],
    "reduce_scatter": ["ring", "recursive_halving", "butterfly",
                       "nonoverlapping"],
    "reduce_scatter_block": ["recursive_halving", "butterfly",
                             "recursive_doubling"],
    "reduce": ["binomial", "pipeline", "chain", "knomial", "rabenseifner",
               "inorder_binary"],
    "allgatherv": ["ring", "linear", "bruck", "sparbit",
                   "neighbor_exchange"],
    "gather": ["binomial", "linear", "linear_sync"],
    "scatter": ["binomial", "linear", "linear_nb"],
    "scan": ["recursive_doubling", "linear"],
    "barrier": ["recursive_doubling", "double_ring", "tree"],
}

SIZES = [64, 1024, 16 << 10, 256 << 10, 2 << 20]


def _run_case(coll: str, alg: str, nbytes: int, ranks: int, iters: int
              ) -> float:
    from ompi_tpu import runtime
    from ompi_tpu.core import var

    var.registry.set_cli(f"coll_tuned_{coll}_algorithm", alg)
    var.registry.reset_cache()
    count = max(ranks, nbytes // 8)

    def fn(ctx):
        c = ctx.comm_world
        send = np.arange(count, dtype=np.float64) + c.rank
        if coll == "bcast":
            args = lambda: (c, send.copy() if c.rank == 0  # noqa: E731
                            else np.zeros(count, np.float64))
            call = lambda a: c.coll.bcast(*a)              # noqa: E731
        elif coll == "allgather":
            call = lambda a: c.coll.allgather(c, send)     # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "reduce_scatter_block":
            buf = np.arange(count - count % ranks, dtype=np.float64)
            call = lambda a: c.coll.reduce_scatter_block(c, buf)  # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "reduce":
            out = np.zeros(count) if c.rank == 0 else None
            call = lambda a: c.coll.reduce(c, send, out, root=0)  # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "gather":
            call = lambda a: c.coll.gather(c, send, root=0)  # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "scatter":
            big = np.arange(count * ranks, dtype=np.float64) \
                if c.rank == 0 else None
            out2 = np.zeros(count)
            call = lambda a: c.coll.scatter(c, big, out2, root=0)  # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "allgatherv":
            counts = [max(1, count // ranks + (1 if r < count % ranks else 0))
                      for r in range(ranks)]
            mine = np.full(counts[c.rank], 1.0)
            call = lambda a: c.coll.allgatherv(   # noqa: E731
                c, mine, counts=counts)
            args = lambda: None                            # noqa: E731
        elif coll == "alltoall":
            big = np.arange(count - count % ranks, dtype=np.float64)
            call = lambda a: c.coll.alltoall(c, big)       # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "reduce_scatter":
            counts = [max(1, count // ranks + (1 if r < count % ranks else 0))
                      for r in range(ranks)]
            big2 = np.arange(sum(counts), dtype=np.float64)
            out3 = np.zeros(counts[c.rank])
            call = lambda a: c.coll.reduce_scatter(   # noqa: E731
                c, big2, out3, counts)
            args = lambda: None                            # noqa: E731
        elif coll == "scan":
            call = lambda a: c.coll.scan(c, send)          # noqa: E731
            args = lambda: None                            # noqa: E731
        elif coll == "barrier":
            call = lambda a: c.coll.barrier(c)             # noqa: E731
            args = lambda: None                            # noqa: E731
        else:
            call = lambda a: c.coll.allreduce(c, send)     # noqa: E731
            args = lambda: None                            # noqa: E731
        call(args())                      # warm transports/matching
        c.coll.barrier(c)
        t0 = time.perf_counter()
        for _ in range(iters):
            call(args())
        c.coll.barrier(c)
        return (time.perf_counter() - t0) / iters

    try:
        res = runtime.run_ranks(ranks, fn, timeout=120)
        return float(np.max(res)) * 1e6
    finally:
        var.registry.set_cli(f"coll_tuned_{coll}_algorithm", "")
        var.registry.reset_cache()


DEVICE_SIZES = [1024, 64 << 10, 1 << 20, 16 << 20]    # bytes per rank


def run_device_sweep(iters: int, sizes=None):
    """Native-ICI vs staged-host timing per (collective, size) on the
    current device mesh — the DEVICE analog of the host sweep, feeding the
    coll/xla decision layer (≙ coll_tuned_decision_fixed.c driven by
    measurement). Returns (rows, winners[coll][bytes] = native|staged)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.parallel import DeviceComm, make_mesh

    ndev = len(jax.devices())
    rows_n = ndev if ndev > 1 else 8
    dc = DeviceComm(make_mesh({"x": ndev}), "x")
    sizes = sizes or DEVICE_SIZES
    rng = np.random.default_rng(0)
    rows, winners = [], {}

    def timed(fn):
        fn()                                   # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters * 1e6

    for nbytes in sizes:
        count = max(rows_n, nbytes // 4)
        count -= count % rows_n          # alltoall reshapes (R, R, c/R)
        host = rng.standard_normal((rows_n, count)).astype(np.float32)
        x = jax.device_put(jnp.asarray(host), dc.sharding())
        x.block_until_ready()
        per = count // rows_n
        vbase = [(per - per // 2) if j % 2 == 0 else (per + per // 2)
                 for j in range(rows_n)]
        C = np.stack([np.roll(vbase, -i) for i in range(rows_n)])
        cases = {
            "allreduce": (
                lambda: dc.allreduce(x).block_until_ready(),
                lambda: jax.device_put(jnp.asarray(np.broadcast_to(
                    np.asarray(jax.device_get(x)).sum(axis=0),
                    host.shape)), dc.sharding()).block_until_ready()),
            "bcast": (
                lambda: dc.bcast(x, 0).block_until_ready(),
                lambda: jax.device_put(jnp.asarray(np.broadcast_to(
                    np.asarray(jax.device_get(x))[0], host.shape)),
                    dc.sharding()).block_until_ready()),
            "reduce_scatter": (
                lambda: dc.reduce_scatter(x).block_until_ready(),
                lambda: jax.device_put(jnp.asarray(
                    np.asarray(jax.device_get(x)).sum(
                        axis=0, dtype=np.float32).reshape(
                        rows_n, count // rows_n)),
                    dc.sharding()).block_until_ready()),
            "alltoall": (
                lambda: dc.alltoall(
                    x.reshape(rows_n, rows_n, count // rows_n)
                ).block_until_ready(),
                lambda: jax.device_put(jnp.asarray(np.ascontiguousarray(
                    np.swapaxes(np.asarray(jax.device_get(x)).reshape(
                        rows_n, rows_n, count // rows_n), 0, 1))),
                    dc.sharding()).block_until_ready()),
        }
        # ragged rows are recorded under the PADDED per-rank bytes the
        # decision layer's _mode computes on the canonical input — a rule
        # emitted from this sweep must match the workload it measured
        # (dense labels would be off by the padding factor)
        eff_bytes = {}
        if per >= 1:
            xp, counts_list = dc.pad_ragged(
                [host[r, :c] for r, c in enumerate(vbase)])
            eff_bytes["allgatherv"] = int(xp.shape[1]) * 4
            cases["allgatherv"] = (
                lambda: dc.allgatherv(xp, counts_list).block_until_ready(),
                lambda: jax.device_put(jnp.asarray(np.broadcast_to(
                    np.concatenate([np.asarray(jax.device_get(xp))[r, :c]
                                    for r, c in enumerate(vbase)])[None],
                    (rows_n, sum(vbase)))),
                    dc.sharding()).block_until_ready())
            cap = dc._bucket(int(C.max()))
            if rows_n * rows_n * cap * 4 <= 1 << 27:
                xb = jax.device_put(jnp.asarray(
                    dc.pack_ragged_blocks(host, C, cap)), dc.sharding())
                out_cap = dc._bucket(int(C.sum(axis=0).max()))
                eff_bytes["alltoallv"] = rows_n * cap * 4

                def staged_a2av():
                    h = np.asarray(jax.device_get(xb))
                    jax.device_put(jnp.asarray(
                        dc.compact_ragged_blocks(h, C, out_cap)),
                        dc.sharding()).block_until_ready()

                cases["alltoallv"] = (
                    lambda: dc.alltoallv(xb, C)[0].block_until_ready(),
                    staged_a2av)
        # third arm: the block-quantized tier (coll/quant) for the
        # quant-capable collectives — a measured quant row in the rules
        # file is the only way the decision layer ever picks it on its
        # own (the platform default never does). ndev > 1 only: on a
        # size-1 axis the quant path degenerates to the local fold and
        # the rule would be meaningless.
        quant_cases = {}
        if ndev > 1:
            quant_cases = {
                "allreduce": (
                    lambda: dc.quant.allreduce(x).block_until_ready()),
                "reduce_scatter": (
                    lambda: dc.quant.reduce_scatter(x)
                    .block_until_ready()),
            }
        for coll, (native, staged) in cases.items():
            nus = timed(native)
            sus = timed(staged)
            arms = {"native": nus, "staged": sus}
            if coll in quant_cases:
                arms["quant"] = timed(quant_cases[coll])
            mode = min(arms, key=arms.get)
            eff = eff_bytes.get(coll, nbytes)
            row = {"coll": coll, "bytes": eff,
                   "nominal_bytes": nbytes,
                   "native_us": round(nus, 1),
                   "staged_us": round(sus, 1), "winner": mode}
            qtxt = ""
            if "quant" in arms:
                row["quant_us"] = round(arms["quant"], 1)
                qtxt = f"quant {arms['quant']:9.1f}us "
            rows.append(row)
            winners.setdefault(coll, {})[eff] = mode
            print(f"device {coll:12s} {eff:>9d}B  native {nus:9.1f}us "
                  f"staged {sus:9.1f}us {qtxt}-> {mode}", flush=True)

    # collective-matmul ring arms: fused unidirectional vs fused
    # bidirectional vs unfused (standalone all_gather/psum_scatter around
    # the dot) per activation size. Winners land as `collmm` rules driving
    # parallel/overlap.decide_collmm — the tp_overlap='fused' hot path
    # picks its ring direction from this measurement, never a guess. The
    # unfused time is recorded as context (staged_us column): the fused
    # kernels replace the GSPMD compose, so rules only arbitrate
    # native (one ring) vs bidir (two half-rings).
    if ndev > 1:
        import jax.numpy as _jnp
        from jax import lax as _lax

        from ompi_tpu.ops.collective_matmul import (allgather_matmul,
                                                    matmul_reduce_scatter)
        from jax.sharding import PartitionSpec as _P

        tp_mesh = make_mesh({"tp": ndev})
        kdim = 256
        out_dt = np.float32

        unfused_ag = jax.jit(jax.shard_map(
            lambda x, w: _jnp.dot(
                _lax.all_gather(x, "tp", tiled=True), w,
                preferred_element_type=out_dt),
            mesh=tp_mesh, in_specs=(_P("tp", None), _P(None, None)),
            out_specs=_P(None, None), check_vma=False))
        unfused_rs = jax.jit(jax.shard_map(
            lambda x, w: _lax.psum_scatter(
                _jnp.dot(x, w, preferred_element_type=out_dt), "tp",
                scatter_dimension=0, tiled=True),
            mesh=tp_mesh, in_specs=(_P(None, "tp"), _P("tp", None)),
            out_specs=_P("tp", None)))

        for nbytes in sizes:
            rows_local = max(2, nbytes // (kdim * 4))
            rows_local -= rows_local % 2       # bidir needs even halves
            m = rows_local * ndev
            per_rank = rows_local * kdim * 4
            xg = jax.device_put(
                jnp.asarray(rng.standard_normal((m, kdim)), jnp.float32),
                jax.sharding.NamedSharding(tp_mesh, _P("tp", None)))
            wg = jnp.asarray(rng.standard_normal((kdim, kdim)), jnp.float32)
            arms = {
                "native": timed(lambda: (
                    allgather_matmul(xg, wg, tp_mesh, "tp")
                    .block_until_ready(),
                    matmul_reduce_scatter(xg, wg, tp_mesh, "tp")
                    .block_until_ready())),
                "bidir": timed(lambda: (
                    allgather_matmul(xg, wg, tp_mesh, "tp",
                                     bidirectional=True)
                    .block_until_ready(),
                    matmul_reduce_scatter(xg, wg, tp_mesh, "tp",
                                          bidirectional=True)
                    .block_until_ready())),
            }
            unfused_us = timed(lambda: (
                unfused_ag(xg, wg).block_until_ready(),
                unfused_rs(xg, wg).block_until_ready()))
            mode = min(arms, key=arms.get)
            rows.append({"coll": "collmm", "bytes": per_rank,
                         "nominal_bytes": nbytes,
                         "native_us": round(arms["native"], 1),
                         "bidir_us": round(arms["bidir"], 1),
                         "staged_us": round(unfused_us, 1),
                         "winner": mode})
            winners.setdefault("collmm", {})[per_rank] = mode
            print(f"device {'collmm':12s} {per_rank:>9d}B  native "
                  f"{arms['native']:9.1f}us bidir {arms['bidir']:9.1f}us "
                  f"unfused {unfused_us:9.1f}us -> {mode}", flush=True)

    # device-window RMA epochs: native program vs staged D2H/host/H2D per
    # payload size — emitted as rma_fence_epoch rules consumed by
    # DeviceWindow._mode (r4 verdict weak#3)
    import os as _os

    from ompi_tpu.core import var as _gvar
    from ompi_tpu.osc import win_allocate_device
    rows_n_win = ndev
    for wcount in (4096, 65536, 1 << 20, 4 << 20):
        nbytes = wcount * 4
        win = win_allocate_device(dc.mesh, (wcount,), axis="x")
        data = jnp.ones((wcount,), jnp.float32)
        hdata = np.ones(wcount, np.float32)

        def epoch(k=[0]):
            k[0] += 1
            win.fence()
            win.put((k[0] + 1) % rows_n_win, data)
            win.accumulate(k[0] % rows_n_win, data)
            h = win.get((k[0] + 2) % rows_n_win, count=wcount)
            win.fence()
            h.value.block_until_ready()

        def run_mode(mode):
            _os.environ["OMPI_TPU_osc_device_mode"] = mode
            _gvar.registry.reset_cache()
            try:
                return timed(epoch)
            finally:
                _os.environ.pop("OMPI_TPU_osc_device_mode", None)
                _gvar.registry.reset_cache()

        nus = run_mode("native")
        sus = run_mode("staged")
        mode = "native" if nus <= sus else "staged"
        rows.append({"coll": "rma_fence_epoch", "bytes": nbytes,
                     "nominal_bytes": nbytes,
                     "native_us": round(nus, 1),
                     "staged_us": round(sus, 1), "winner": mode})
        winners.setdefault("rma_fence_epoch", {})[nbytes] = mode
        print(f"device rma_fence_epoch {nbytes:>9d}B  native {nus:9.1f}us "
              f"staged {sus:9.1f}us -> {mode}", flush=True)
        win.free()
    return rows, winners


def run_hier_sweep(iters: int, sizes=None,
                   dcn_us_per_mib: float = 200.0):
    """Hier-vs-flat allreduce sweep on a simulated two-tier mesh: the
    devices fold into an outer×inner (2 × n/2) grid with the outer axis
    force-classified DCN (``topo_sim_dcn_axes``), and each size times
    the flat tuple-axis psum against the staged HAN form (and its
    quantized-outer composition).  Because the raw kernels run on one
    host fabric, the DCN skew enters ANALYTICALLY: each arm's measured
    µs is topped up by its slow-plane bytes × ``dcn_us_per_mib`` — the
    exact per-arm figures the simulated-DCN shim would charge at
    dispatch (hierarchy.hier_wire_bytes is the shared source of truth).
    Winners land under the ``allreduce@dcn`` key, so emit_device_rules
    writes PER-PLANE rows the '<coll>@<plane>' grammar consumes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as _P

    from ompi_tpu.core import var
    from ompi_tpu.parallel import make_mesh, simdcn
    from ompi_tpu.parallel.hierarchy import (hier_wire_bytes,
                                             hierarchical_psum,
                                             hierarchical_psum_quant)

    ndev = len(jax.devices())
    if ndev < 4 or ndev % 2:
        print(f"hier sweep needs an even device count >= 4 (have {ndev});"
              " skipping", flush=True)
        return [], {}
    no, ni = 2, ndev // 2
    var.registry.set_cli("topo_sim_dcn_axes", "outer")
    var.registry.reset_cache()
    simdcn.clear_cache()
    try:
        mesh = make_mesh({"outer": no, "inner": ni})
        spec = _P(("outer", "inner"))
        rng = np.random.default_rng(0)
        rows, winners = [], {}

        def timed(fn):
            fn()
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            return (time.perf_counter() - t0) / iters * 1e6

        def build(kind):
            def fn(xs):
                flat = xs.reshape(-1)
                if kind == "hier":
                    out = hierarchical_psum(flat, "inner", "outer")
                elif kind == "hier+quant":
                    out = hierarchical_psum_quant(flat, "inner", "outer",
                                                  no)
                else:
                    out = jax.lax.psum(flat, ("outer", "inner"))
                return out.reshape(xs.shape)
            return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                      out_specs=spec))

        fns = {k: build(k) for k in ("native", "hier", "hier+quant")}
        frac = simdcn.ring_dcn_fraction(mesh, ("outer", "inner"))
        for nbytes in sizes or DEVICE_SIZES:
            count = max(ndev, nbytes // 4)
            count -= count % (ndev * ni)     # divisible: no pad noise
            x = jax.device_put(
                jnp.asarray(rng.standard_normal((ndev, count // ndev)),
                            jnp.float32),
                jax.sharding.NamedSharding(mesh, spec))
            x.block_until_ready()
            per = count // ndev
            eff = per * 4
            hw = hier_wire_bytes(per, np.float32, ni, no)
            hwq = hier_wire_bytes(per, np.float32, ni, no, quant=True)
            dcn_bytes = {
                "native": int(2 * (ndev - 1) / ndev * eff * frac),
                "hier": hw["outer_bytes"],
                "hier+quant": hwq["outer_bytes"],
            }
            arms = {}
            for kind, fn in fns.items():
                us = timed(lambda f=fn: f(x).block_until_ready())
                arms[kind] = us + simdcn.penalty_us(
                    dcn_bytes[kind], dcn_us_per_mib)
            mode = min(arms, key=arms.get)
            rows.append({"coll": "allreduce@dcn", "bytes": eff,
                         "nominal_bytes": nbytes,
                         "native_us": round(arms["native"], 1),
                         "hier_us": round(arms["hier"], 1),
                         "hier_quant_us": round(arms["hier+quant"], 1),
                         "dcn_bytes": dcn_bytes,
                         "winner": mode})
            winners.setdefault("allreduce@dcn", {})[eff] = mode
            print(f"device {'allreduce@dcn':14s} {eff:>9d}B  native "
                  f"{arms['native']:9.1f}us hier {arms['hier']:9.1f}us "
                  f"hier+quant {arms['hier+quant']:9.1f}us -> {mode}",
                  flush=True)
        return rows, winners
    finally:
        var.registry.set_cli("topo_sim_dcn_axes", "")
        var.registry.reset_cache()
        simdcn.clear_cache()


def emit_device_rules(winners: dict, path: str,
                      platform: str = "unknown",
                      provenance: str = None) -> None:
    """Winners → a coll/xla dynamic-rules file: one line per mode change
    walking sizes ascending (rules apply at >= min_bytes, later lines win,
    matching _load_device_rules/_mode semantics). The header records the
    fabric that produced the numbers — a cpu-derived ruleset applied on a
    real TPU would override the correct native-always platform default.
    ``provenance`` (a ``# learned from PERF_LEDGER ...`` line) is kept in
    the header so a ledger-derived file stays distinguishable from a
    sweep-measured one across re-emits (rules_provenance round-trips it)."""
    lines = [f"# device decision rules measured by coll_tune --device "
             f"on platform={platform}",
             "# <coll>[@<plane>] <min_ndev> <min_bytes> "
             "<native|staged|quant|hier|hier+quant>"]
    if provenance:
        lines.insert(1, provenance if provenance.startswith("#")
                     else f"# {provenance}")
    for coll, by_size in winners.items():
        prev = None
        for nbytes in sorted(by_size):
            mode = by_size[nbytes]
            if mode != prev:
                # min_ndev 1: the rules were measured on THIS mesh — they
                # must also match when it has a single device (the 1-chip
                # TPU box), so no device-count gate is encoded
                lines.append(f"{coll} 1 {0 if prev is None else nbytes} "
                             f"{mode}")
                prev = mode
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


_PROVENANCE_TAG = "# learned from PERF_LEDGER"


def rules_provenance(path: str):
    """The ``# learned from PERF_LEDGER <path>`` header line of a rules
    file, or None for a sweep-measured file. The loader side
    (coll/xla._load_device_rules) skips every comment, so a
    ledger-derived file parses identically — this accessor is how the
    provenance ROUND-TRIPS: read it here, hand it back to
    emit_device_rules, and the re-emitted file carries the same line."""
    with open(path) as fh:
        for line in fh:
            if line.strip().startswith(_PROVENANCE_TAG):
                return line.strip()
    return None


def emit_learned_rules(ledger_path: str, out_path: str,
                       min_count: int = 1) -> dict:
    """--from-ledger: render the perf cost model's measured crossovers
    (best modeled busbw per (coll, log2-size-bucket)) into
    DEVICE_RULES-compatible rows, provenance-tagged, so static-rules
    deployments inherit learned crossovers without opting into
    coll_xla_rules="learned". Returns the winners dict that was emitted."""
    from ..perf.model import CostModel, load_ledger_doc

    m = CostModel()
    ledger = load_ledger_doc(ledger_path)
    m.load_json(ledger.get("buckets", {}))
    winners: dict = {}
    for coll, rows in m.crossovers(min_count=min_count).items():
        for bucket_bytes, arm in rows:
            winners.setdefault(coll, {})[bucket_bytes] = arm
    emit_device_rules(winners, out_path,
                      platform=str(ledger.get("platform") or "unknown"),
                      provenance=f"{_PROVENANCE_TAG} {ledger_path}")
    return winners


def explain_rules(rules_path: str, winners: dict, quiet: bool = False):
    """Round-trip the just-emitted rules file through the coll/xla
    decision layer: re-dispatch one collective per (coll, bytes) sweep
    row with tracing on and print ``trace.explain_last`` — the arm the
    decision layer picks under the new rules and the precedence link
    that chose it (force var / blanket / rules row / floor veto).  A row
    whose decided arm differs from the measured winner is exactly the
    drift the audit exists to surface (e.g. a quant winner held under
    the coll_quant_min_bytes floor)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu import runtime, trace
    from ompi_tpu.core import var
    from ompi_tpu.parallel import attach_mesh, make_mesh

    ndev = len(jax.devices())
    rows_n = ndev if ndev > 1 else 8
    dispatched = ("allreduce", "bcast", "reduce_scatter", "alltoall")
    var.registry.set_cli("coll_xla_dynamic_rules", rules_path)
    var.registry.reset_cache()
    trace.enable()
    try:
        def fn(ctx):
            c = ctx.comm_world
            attach_mesh(c, make_mesh({"x": ndev}), "x")
            lines = []
            for coll in dispatched:
                for nbytes in sorted(winners.get(coll, {})):
                    count = max(rows_n, int(nbytes) // 4)
                    count -= count % rows_n
                    x = jax.device_put(
                        jnp.ones((rows_n, count), jnp.float32),
                        c.device_comm.sharding())
                    if coll == "allreduce":
                        c.coll.allreduce(c, x)
                    elif coll == "bcast":
                        c.coll.bcast(c, x)
                    elif coll == "reduce_scatter":
                        c.coll.reduce_scatter(
                            c, x, None, [count // rows_n] * rows_n)
                    else:
                        c.coll.alltoall(c, x.reshape(
                            rows_n, rows_n, count // rows_n))
                    exp = trace.explain_last(coll)
                    if exp is not None:
                        lines.append(
                            f"explain {coll:14s} {int(nbytes):>9d}B -> "
                            f"{exp['arm']:6s} (measured "
                            f"{winners[coll][nbytes]:6s}) "
                            f"because {exp['reason']}")
            return lines

        lines = runtime.run_ranks(1, fn, timeout=300)[0]
        if not quiet:
            for line in lines:
                print(line, flush=True)
        return lines
    finally:
        trace.disable()
        var.registry.set_cli("coll_xla_dynamic_rules", "")
        var.registry.reset_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="TUNE_SWEEP.json")
    ap.add_argument("--device", action="store_true",
                    help="Sweep the DEVICE path (native ICI vs staged "
                         "host) and emit coll/xla decision rules.")
    ap.add_argument("--device-rules-out", default=None)
    ap.add_argument("--from-ledger", default=None, metavar="LEDGER.json",
                    help="Render a PERF_LEDGER (ompi_tpu/perf cost "
                         "model) into DEVICE_RULES-compatible rows with "
                         "a provenance comment; no sweep is run. "
                         "Writes --device-rules-out (default "
                         "DEVICE_RULES_learned.txt).")
    ap.add_argument("--platform", default=None,
                    help="Force a jax platform (e.g. cpu) through "
                         "jax.config before any backend initializes.")
    args = ap.parse_args(argv)
    if args.platform and not args.device:
        ap.error("--platform only applies to --device (the host sweep "
                 "never initializes jax)")

    if args.from_ledger:
        out = args.device_rules_out or "DEVICE_RULES_learned.txt"
        winners = emit_learned_rules(args.from_ledger, out)
        n_rules = sum(len(v) for v in winners.values())
        print(f"wrote {out}: {n_rules} learned crossover(s) over "
              f"{len(winners)} collective(s) from {args.from_ledger}")
        if not winners:
            print("ledger holds no modeled cells — emitted a header-only "
                  "rules file")
        return 0

    if args.device:
        if args.platform == "cpu" and "host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            # a 1-device cpu sweep would emit degenerate rules (native
            # arms become no-ops over a size-1 axis) — force the 8-way
            # virtual mesh the CPU test suite runs on
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        import jax

        if args.platform:
            jax.config.update("jax_platforms", args.platform)

        rows, winners = run_device_sweep(args.iters)
        hrows, hwinners = run_hier_sweep(args.iters)
        rows += hrows
        winners.update(hwinners)
        platform = jax.devices()[0].platform
        args.device_rules_out = args.device_rules_out or "DEVICE_RULES.txt"
        emit_device_rules(winners, args.device_rules_out,
                          platform=platform)
        out = {"ndev": len(jax.devices()), "iters": args.iters,
               "platform": platform,
               "winners": {c: {str(k): v for k, v in w.items()}
                           for c, w in winners.items()},
               "results": rows}
        with open(args.out if args.out != "TUNE_SWEEP.json"
                  else "TUNE_DEVICE.json", "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"wrote {args.device_rules_out}")
        # decision-audit round trip: why does each sweep row take its arm
        # under the rules we just wrote?
        explain_rules(args.device_rules_out, winners)
        return 0

    rows = []
    winners: dict = {}
    for coll, algs in ALGS.items():
        sizes = SIZES if coll != "barrier" else SIZES[:1]  # no payload
        for nbytes in sizes:
            best = (None, float("inf"))
            for alg in algs:
                pof2 = (args.ranks & (args.ranks - 1)) == 0
                if alg == "recursive_doubling" and not pof2 and \
                        coll in ("allgather", "reduce_scatter_block"):
                    continue
                if alg == "recursive_halving" and not pof2 and \
                        coll in ("reduce_scatter", "reduce_scatter_block"):
                    # non-pof2 dispatch substitutes butterfly — measuring
                    # it under this label would record a winner that can
                    # never actually run
                    continue
                if alg == "neighbor_exchange" and args.ranks % 2:
                    continue
                try:
                    us = _run_case(coll, alg, nbytes, args.ranks, args.iters)
                except Exception as exc:   # record, keep sweeping
                    rows.append({"coll": coll, "alg": alg, "bytes": nbytes,
                                 "error": repr(exc)})
                    continue
                rows.append({"coll": coll, "alg": alg, "bytes": nbytes,
                             "us": round(us, 1)})
                print(f"{coll:22s} {alg:20s} {nbytes:>9d}B  {us:10.1f}us",
                      flush=True)
                if us < best[1]:
                    best = (alg, us)
            winners.setdefault(coll, {})[str(nbytes)] = best[0]
    out = {
        "ranks": args.ranks,
        "iters": args.iters,
        "note": "single-core host: rankings are the signal, not abs us",
        "winners": winners,
        "results": rows,
    }
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
