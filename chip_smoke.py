"""Chip smoke: the framework's main path, end to end, on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip paths on a 4-chip host

One chip runs, in order: the device line; the collectives through the
communicator's coll/xla component (allreduce, allgather, alltoall,
alltoallv, 8 B - 64 MiB per rank, exact against numpy, every call audited
``native``); ``flagship_config`` training through ``make_train_step``
(finite falling loss, the Pallas kernels compiled, donated buffers
aliased); ``ServingEngine`` + ``ContinuousBatchingScheduler`` at flagship
widths against the float32 ``forward()``; and the CG solver of
``examples/stencil.py`` on an HBM-resident grid.

``--chips 4`` runs only what exists across chips: (a) ``tpurun -np 4
--chips-per-rank 1`` of ``examples/device_allreduce.py``, before this
process touches JAX; (b) the collectives over a 4-device mesh; (c) a
dp=2 x tp=2 flagship train step against the one-device loss; (d) a tp=4
``ServingEngine`` against the float32 reference.

Each phase prints one line; any failure raises and exits non-zero.  The
last stdout line is the result object, printed only when every phase
passed.  There is no CPU branch: without a TPU the script exits at the
device line.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MiB = 1 << 20
TRAIN_BATCH = 4          # flagship batch on one v5e chip (16 GB HBM)
CG_N = 16384             # CG grid edge: 1 GiB f32 per vector in HBM
CG_ITERS = 50
# serving: bf16 weights + KV cache vs the float32 train-layout forward();
# relative L2 error of each position's logit vector
SERVE_RTOL = 5e-2


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- (a) rank-per-chip launch: runs before this process imports jax ---------

def phase_rank_per_chip(timeout_s: float = 180.0) -> None:
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-np", "4",
           "--chips-per-rank", "1",
           os.path.join(HERE, "examples", "device_allreduce.py")]
    t0 = time.perf_counter()
    p = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True,
                         start_new_session=True)
    try:
        out, _ = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(p.pid, signal.SIGKILL)
        out, _ = p.communicate()
        raise AssertionError(f"rank-per-chip: tpurun hung past "
                             f"{timeout_s:.0f} s; tail:\n{out[-4000:]}")
    ok = out.count("coll/xla path ok")
    if p.returncode != 0 or ok != 4:
        raise AssertionError(f"rank-per-chip: rc={p.returncode}, "
                             f"{ok}/4 ranks ok; tail:\n{out[-4000:]}")
    log("rank_per_chip", ranks=4, rc=p.returncode,
        seconds=round(time.perf_counter() - t0, 1))


# -- collectives through the communicator -----------------------------------

def _a2av_counts(R: int, count: int) -> np.ndarray:
    """Uneven circulant split of ``count`` per row (column sums conserved)."""
    per = count // R
    base = [(per - per // 2) if j % 2 == 0 else (per + per // 2)
            for j in range(R)]
    base[-1] += count - sum(base)
    return np.stack([np.roll(base, -i) for i in range(R)]).astype(np.int64)


def _a2av_reference(x: np.ndarray, C: np.ndarray) -> list:
    R = x.shape[0]
    soff = np.concatenate([np.zeros((R, 1), np.int64),
                           np.cumsum(C, axis=1)[:, :-1]], axis=1)
    return [np.concatenate([x[i, soff[i, j]:soff[i, j] + C[i, j]]
                            for i in range(R)]) for j in range(R)]


def phase_collectives(mesh, R: int, sizes, ops, rng, tag: str) -> None:
    """Every op at every per-rank size, exact against numpy, audited."""
    import jax

    from ompi_tpu import runtime, trace
    from ompi_tpu.op import SUM
    from ompi_tpu.parallel import DeviceComm, attach_mesh

    ctx = runtime.init()
    comm = ctx.comm_world
    attach_mesh(comm, mesh, "x")
    dc = comm.device_comm
    check(isinstance(dc, DeviceComm), "attach_mesh gave no DeviceComm")
    ndev = len(set(mesh.devices.flat))
    check(ndev == mesh.devices.size, f"mesh repeats a device: {mesh}")
    trace.enable()
    t0 = time.perf_counter()
    calls = 0
    try:
        for nbytes in sizes:
            count = max(nbytes // 4, R)      # >= one element per peer
            count -= count % R
            for op in ops:
                if op == "allreduce":
                    h = rng.integers(0, 256, (R, count)).astype(np.float32)
                    out = comm.coll.allreduce(comm, dc.from_ranks(list(h)),
                                              op=SUM)
                    want = np.broadcast_to(h.sum(0), h.shape)
                    audit = "allreduce"
                elif op == "allgather":
                    h = rng.integers(0, 256, (R, count)).astype(np.float32)
                    out = comm.coll.allgather(comm, dc.from_ranks(list(h)))
                    want = np.broadcast_to(h.reshape(-1), (R, R * count))
                    audit = "allgather"
                elif op == "reduce_scatter":
                    h = rng.integers(0, 256, (R, count)).astype(np.float32)
                    out = comm.coll.reduce_scatter_block(
                        comm, dc.from_ranks(list(h)), op=SUM)
                    want = h.sum(0).reshape(R, count // R)
                    audit = "reduce_scatter_block"
                elif op == "alltoall":
                    h = rng.integers(0, 256, (R, R, count // R)
                                     ).astype(np.float32)
                    out = comm.coll.alltoall(comm, dc.from_ranks(list(h)))
                    want = np.swapaxes(h, 0, 1)
                    audit = "alltoall"
                elif op == "alltoallv":
                    h = rng.integers(0, 256, (R, count)).astype(np.float32)
                    C = _a2av_counts(R, count)
                    out = comm.coll.alltoallv(comm, dc.from_ranks(list(h)),
                                              None, C, C.sum(axis=0))
                    want = None
                    audit = "alltoallv"
                else:
                    raise ValueError(op)
                jax.block_until_ready(out)
                devs = {s.device for s in out.addressable_shards}
                check(len(devs) == ndev,
                      f"{op} {nbytes} B: result on {len(devs)} device(s), "
                      f"mesh has {ndev}")
                got = np.asarray(jax.device_get(out))
                if want is None:
                    for j, row in enumerate(_a2av_reference(h, C)):
                        check(np.array_equal(got[j, :row.size], row),
                              f"alltoallv {nbytes} B: row {j} differs")
                else:
                    check(got.shape == want.shape and
                          np.array_equal(got, want),
                          f"{op} {nbytes} B: result differs from numpy")
                rec = trace.explain_last(audit)
                check(rec is not None and rec["arm"] == "native",
                      f"{op} {nbytes} B: audited arm "
                      f"{rec and rec['arm']!r}, want 'native'")
                calls += 1
                del out, got
    finally:
        trace.disable()
        trace.clear()
        runtime.finalize()
    log(tag, ndev=ndev, ranks=R, calls=calls, ops=",".join(ops),
        sizes=f"{sizes[0]}B..{sizes[-1] // MiB}MiB", exact=True,
        arm="native", seconds=round(time.perf_counter() - t0, 1))


# -- flagship training on one chip ------------------------------------------

def _tokens(cfg, batch: int, seed: int):
    import jax
    import jax.numpy as jnp
    return jax.random.randint(jax.random.key(seed + 1),
                              (batch, cfg.seq + 1), 0, cfg.vocab, jnp.int32)


def phase_train(cfg, batch: int, seed: int, steps: int = 5) -> None:
    import jax

    from ompi_tpu.models.transformer import init_params, make_train_step

    params = init_params(jax.random.key(seed), cfg)
    init_opt, step = make_train_step(cfg)
    opt = init_opt(params)
    tokens = _tokens(cfg, batch, seed)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves((params, opt)))
    t0 = time.perf_counter()
    compiled = step.jitted.lower(params, opt, tokens).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    check("tpu_custom_call" in hlo,
          "train step HLO holds no tpu_custom_call: flash did not "
          "compile to a Mosaic kernel")
    check("input_output_alias" in hlo,
          "train step HLO has no input_output_alias: donation dropped")
    alias = int(mem.alias_size_in_bytes)
    check(alias >= 0.9 * state_bytes,
          f"donation aliased {alias} B of {state_bytes} B of "
          "params + optimizer state")
    del compiled, hlo
    losses, times = [], []
    for _ in range(steps):
        t1 = time.perf_counter()
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))           # blocks on the step
        times.append(time.perf_counter() - t1)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    stats = jax.devices()[0].memory_stats() or {}
    log("train", config="flagship_config", batch=batch, seq=cfg.seq,
        params_m=round(sum(x.size for x in jax.tree.leaves(params)) / 1e6,
                       1),
        losses=",".join(f"{v:.4f}" for v in losses),
        step_ms=round(1e3 * float(np.median(times[1:])), 1),
        compile_s=round(compile_s, 1), first_step_s=round(times[0], 2),
        aliased_bytes=alias,
        state_bytes=state_bytes, tpu_custom_call=True,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    del params, opt, tokens, loss, step, init_opt
    gc.collect()


# -- serving ----------------------------------------------------------------

def _relerr(a, b) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_serve(cfg, mesh, seed: int, n_req: int, prompt_len, max_new: int,
                tag: str) -> None:
    """Requests through the scheduler, then one request teacher-forced
    against the float32 train-layout forward() (tests/test_serving.py's
    reference)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel import DeviceComm
    from ompi_tpu.serving.engine import ServingEngine
    from ompi_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                            Request)

    rng = np.random.default_rng(seed)
    params = tfm.init_params(jax.random.key(seed + 2), cfg)
    dc = DeviceComm(mesh, "tp")
    page = 16
    per_seq = -(-(prompt_len[1] + max_new) // page)
    eng = ServingEngine(dc, tfm.shard_params(params, mesh, cfg), cfg,
                        n_pages=n_req * per_seq + 1, page_size=page,
                        max_seqs=n_req)   # +1: the scratch page
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab, int(rng.integers(prompt_len[0], prompt_len[1] + 1))
    ).astype(np.int32), max_new=max_new) for i in range(n_req)]
    t0 = time.perf_counter()
    sched = ContinuousBatchingScheduler(eng, reqs)
    sched.run()
    run_s = time.perf_counter() - t0
    check(sorted(sched.results) == list(range(n_req)),
          f"scheduler finished {sorted(sched.results)}")
    for rid, res in sched.results.items():
        check(len(res["tokens"]) == max_new,
              f"request {rid}: {len(res['tokens'])} tokens, want {max_new}")

    # teacher-forced: feed request 0's own tokens back, compare logits
    req, gen = reqs[0], sched.results[0]["tokens"]
    slot = eng.cache.admit(len(req.prompt), max_new)
    _first, lg = eng.prefill(slot, req.prompt)
    got = [np.asarray(lg, np.float32).reshape(-1, cfg.vocab)[0]]
    for i in range(max_new - 1):
        t = np.zeros(eng.max_seqs, np.int32)
        p = np.full(eng.max_seqs, -1, np.int64)
        t[slot] = gen[i]
        p[slot] = int(eng.cache.seq_lens[slot])
        _nxt, lg = eng.decode_step(t, p)
        eng.cache.seq_lens[slot] += 1
        got.append(np.asarray(lg, np.float32)[0, slot])
    eng.cache.release(slot)
    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32, attn="dense")
    seq = np.concatenate([req.prompt, np.asarray(gen[:-1], np.int32)])
    ref = np.asarray(jax.jit(lambda p, t: tfm.forward(p, t, ref_cfg))(
        params, jnp.asarray(seq[None])))[0, len(req.prompt) - 1:]
    errs = [_relerr(g, r) for g, r in zip(got, ref)]
    agree = float(np.mean([int(np.argmax(g) == np.argmax(r))
                           for g, r in zip(got, ref)]))
    check(max(errs) <= SERVE_RTOL,
          f"serving logits off the float32 reference: max rel err "
          f"{max(errs):.4f} > {SERVE_RTOL}")
    log(tag, tp=dc.n, requests=n_req, max_new=max_new,
        prompt_lens=",".join(str(len(r.prompt)) for r in reqs),
        run_s=round(run_s, 2), compared_positions=len(errs),
        max_rel_err=round(max(errs), 5), rtol=SERVE_RTOL,
        argmax_agree=round(agree, 3))
    del eng, params, sched
    gc.collect()


# -- CG on an HBM-resident grid ---------------------------------------------

def _cg_reference(b: np.ndarray, iters: int) -> np.ndarray:
    """Plain float64 CG on the 5-point Dirichlet Laplacian."""
    def lap(u):
        p = np.pad(u, 1)
        return 4 * u - p[:-2, 1:-1] - p[2:, 1:-1] - p[1:-1, :-2] - p[1:-1, 2:]
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rr = float(np.vdot(r, r))
    out = []
    for _ in range(iters):
        ap = lap(p)
        alpha = rr / float(np.vdot(p, ap))
        x += alpha * p
        r -= alpha * ap
        rr_new = float(np.vdot(r, r))
        p = r + (rr_new / rr) * p
        rr = rr_new
        out.append(np.sqrt(rr))
    return np.asarray(out)


def phase_cg(mesh, n: int, iters: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, os.path.join(HERE, "examples"))
    from stencil import cg_solver

    # the solver against float64 numpy on a small grid first
    small = np.asarray(jax.random.normal(jax.random.key(seed + 3),
                                         (256, 256), jnp.float32))
    got = np.asarray(cg_solver(mesh, 256, iters)(
        jax.device_put(small, NamedSharding(mesh, P("x"))))[1])
    want = _cg_reference(small.astype(np.float64), iters)
    check(np.allclose(got, want, rtol=1e-3),
          f"CG residuals differ from float64 numpy: {got[-3:]} vs "
          f"{want[-3:]}")

    sharding = NamedSharding(mesh, P("x"))
    b = jax.jit(lambda k: jax.random.normal(k, (n, n), jnp.float32),
                out_shardings=sharding)(jax.random.key(seed + 4))
    b_norm = float(jnp.linalg.norm(b))
    solve = cg_solver(mesh, n, iters)
    t0 = time.perf_counter()
    x, res = jax.block_until_ready(solve(b))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    x, res = jax.block_until_ready(solve(b))
    dt = time.perf_counter() - t0
    res = np.asarray(res)
    check(np.all(np.isfinite(res)), "CG residual not finite")
    check(res[-1] < 0.5 * res[0] < b_norm,
          f"CG residual did not fall: |b|={b_norm:.4g} "
          f"r1={res[0]:.4g} r{iters}={res[-1]:.4g}")
    log("cg", grid=f"{n}x{n}", iters=iters, b_norm=round(b_norm, 2),
        res_first=round(float(res[0]), 3), res_last=round(float(res[-1]), 3),
        small_grid_vs_numpy="ok", first_call_s=round(first_s, 2),
        solve_ms=round(dt * 1e3, 2))
    del x, b, res
    gc.collect()


# -- four chips: dp x tp training, tp serving -------------------------------

def phase_train_dp_tp(cfg, batch: int, seed: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu.models.transformer import (init_params, loss_fn,
                                             make_train_step, shard_params)
    from ompi_tpu.parallel import make_mesh

    devs = jax.devices()
    params = init_params(jax.random.key(seed), cfg)
    tokens = _tokens(cfg, batch, seed)

    one = make_mesh({"dp": 1, "tp": 1}, devices=devs[:1])
    p1 = shard_params(params, one, cfg)
    t1 = jax.device_put(tokens, NamedSharding(one, P("dp", None)))
    ref = float(jax.jit(lambda p, t: loss_fn(p, t, cfg, one))(p1, t1))
    del p1, t1

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devs[:4])
    check(len(set(mesh.devices.flat)) == 4, "dp x tp mesh is not 4 chips")
    p4 = shard_params(params, mesh, cfg)
    del params
    init_opt, step = make_train_step(cfg, mesh)
    opt = init_opt(p4)
    t4 = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    losses = []
    for _ in range(2):
        p4, opt, loss = step(p4, opt, t4)
        losses.append(float(loss))
    spread = {s.device for leaf in jax.tree.leaves(p4)
              for s in leaf.addressable_shards}
    check(len(spread) == 4, f"params live on {len(spread)} device(s)")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(abs(losses[0] - ref) <= 1e-2 * abs(ref),
          f"dp2 x tp2 first-step loss {losses[0]:.5f} vs one-device "
          f"{ref:.5f}")
    check(losses[1] < losses[0], f"loss did not fall: {losses}")
    log("train_dp2_tp2", batch=batch, seq=cfg.seq,
        one_device_loss=round(ref, 5),
        losses=",".join(f"{v:.5f}" for v in losses),
        rel_diff=f"{abs(losses[0] - ref) / abs(ref):.2e}")
    del p4, opt, t4, loss, step
    gc.collect()


# -- driver -----------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.chips == 4:
        phase_rank_per_chip()        # the children own the chips; no jax yet

    import jax

    from ompi_tpu.runtime import use_compile_cache

    cache_dir = use_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def _count(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(_count)

    devs = jax.devices()
    d0 = devs[0]
    log("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs), compile_cache=cache_dir)
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {d0.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 2

    from ompi_tpu.models.transformer import flagship_config
    from ompi_tpu.parallel import make_mesh

    rng = np.random.default_rng(args.seed)
    cfg = flagship_config()
    if args.chips == 1:
        phase_collectives(make_mesh({"x": 1}, devices=devs[:1]), 4,
                          [8, 64 << 10, MiB, 16 * MiB, 64 * MiB],
                          ("allreduce", "allgather", "alltoall", "alltoallv"),
                          rng, "collectives")
        phase_train(cfg, TRAIN_BATCH, args.seed)
        phase_serve(cfg, make_mesh({"tp": 1}, devices=devs[:1]), args.seed,
                    n_req=8, prompt_len=(128, 512), max_new=32,
                    tag="serve")
        phase_cg(make_mesh({"x": 1}, devices=devs[:1]), CG_N, CG_ITERS,
                 args.seed)
    else:
        phase_collectives(make_mesh({"x": 4}, devices=devs[:4]), 4,
                          [8, MiB, 64 * MiB],
                          ("allreduce", "allgather", "reduce_scatter",
                           "alltoall", "alltoallv"), rng, "collectives_4")
        phase_train_dp_tp(cfg, TRAIN_BATCH, args.seed)
        phase_serve(cfg, make_mesh({"tp": 4}, devices=devs[:4]), args.seed,
                    n_req=4, prompt_len=(128, 512), max_new=16,
                    tag="serve_tp4")
    log("compile_cache", dir=cache_dir, hits=cache["hits"],
        misses=cache["misses"])
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
