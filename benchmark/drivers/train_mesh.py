"""Training through ``make_train_step(cfg, mesh)`` on a ``dp`` x ``tp`` mesh.

As ``drivers/train.py``, whose batches, faults and comparison it imports:
set-up builds the cell's mesh over its chips (``make_mesh``), places the
params drawn from the seed with the program's ``shard_params`` (its
Megatron layout), makes the AdamW state and the jitted step, and places
each batch of the pool dp-sharded; it runs the first three steps through
the window's own call and feed.  The window dispatches each step before it
blocks on the loss of the one before, and ends when the last loss is
ready.

Set-up also reads the compiled step's collectives from the program
(``step.comm_graph``, ``analysis.commgraph.from_compiled``): the wire
bytes each chip moves a step per mesh axis, and the axes of each
collective instruction by name, which the per-layer readers use.

``train_tokens_per_s`` = batch x seq x steps in the window / window.

Correctness, once the window has closed and the program's state is freed:
the float32 reference of ``benchmark/references/olmo_mesh.py`` runs the
same three steps from the same params and rows over the same chips, in a
layout of its own, and ``drivers/train.py``'s three numbers are compared.
"""

from __future__ import annotations

import time

from benchmark import comm_bytes, flops
from benchmark.drivers import train as one_chip
from benchmark.references import olmo as ref
from benchmark.references import olmo_mesh

def _broken(impl, step, data):
    """``drivers/train.py``'s planted faults; half the batch is placed
    dp-sharded again, as the step takes its batch."""
    if impl == "fault:half_batch":
        import jax
        return lambda params, opt, tokens: step(
            params, opt, jax.device_put(tokens[:tokens.shape[0] // 2], data))
    return one_chip._broken(impl, step)


def run(cell, *, seed, seconds, window, devices, impl="program"):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.run import log, memory_peak_bytes, seed32
    # the program must read its own compiled step; without that the cell
    # cannot run, and says so before it builds anything
    from ompi_tpu.analysis.commgraph import from_compiled  # noqa: F401
    from ompi_tpu.models.transformer import make_train_step, shard_params
    from ompi_tpu.parallel.mesh import make_mesh

    cfg = cell["config_data"]
    pcfg = one_chip.program_config(cfg, cell)
    mesh = make_mesh(dict(cell["mesh"]), devices)
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    B, S = cell["batch"], cell["seq"]
    data = NamedSharding(mesh, P("dp", None))
    key = jax.random.key(seed32(seed))
    init = ref.make_init(cfg)
    with jax.default_device(devices[0]):
        params = shard_params(init(key), mesh, pcfg)
        batches = [jax.device_put(b, data) for b in one_chip.make_batches(
            cell, cfg, seed, jax.random.fold_in(key, 1))]
    init_opt, step = make_train_step(pcfg, mesh)
    opt = init_opt(params)
    graph_of = step.comm_graph
    if impl != "program":
        step = _broken(impl, step, data)
    n_check = int(cell["check_steps"])

    # the first steps, through the window's own call and feed
    losses = []
    for i in range(n_check):
        params, opt, loss = step(params, opt, batches[i])
        losses.append(float(loss))
        if i == 0:
            g1 = ref.leaf_norms(opt[0].mu) / (1 - ref.ADAM["b1"])
    change = ref.change_norms(init, key, params)
    prog = {"losses": losses, "grad_norms": g1, "change_norms": change}
    graph = graph_of(params, opt, batches[0])
    wire = graph.wire_by_axes(mesh)

    pc = time.perf_counter
    n_pool = len(batches)
    i, steps, pending = n_check, 0, None
    t_end = window.open() + seconds
    while True:
        with window.span("train.step"):
            params, opt, loss = step(params, opt, batches[i % n_pool])
        i += 1
        steps += 1
        if pending is not None:
            with window.span("train.block"):
                float(pending)
        pending = loss
        if pc() >= t_end:
            break
    last = float(pending)
    window.close()
    mem = memory_peak_bytes(devices)
    del params, opt, pending, loss
    check_batches = batches[:n_check]
    del batches

    t_ref = pc()
    refr = olmo_mesh.run_reference(cfg, key, check_batches, devices,
                                   steps=n_check)
    ref_s = pc() - t_ref

    checks = one_chip.compare(prog, refr, cell["limits"])
    tokens = steps * B * S
    fpt = flops.train_flops_per_token(
        cfg["d_model"], cfg["n_layers"], cfg["n_heads"], cfg["head_dim"],
        cfg["mlp_hidden_size"], cfg["embedding_size"], S)
    tps = tokens / window.seconds
    wire_mb = {"+".join(ax) or "none": b / 1e6 for ax, b in wire.items()}
    log("train_mesh", mesh=f"dp{dp}xtp{tp}", steps=steps, tokens=tokens,
        window_s=round(window.seconds, 6),
        step_ms=round(window.seconds / steps * 1e3, 3),
        losses=",".join(f"{v:.6f}" for v in losses),
        ref_losses=",".join(f"{v:.6f}" for v in refr["losses"]),
        last_loss=round(last, 6), reference_s=round(ref_s, 2),
        wire_MB=wire_mb, flops_per_token=fpt)
    return {
        "attempted": steps,
        "failed": 0,
        "memory_peak_bytes": mem,
        "e2e": {"train_tokens_per_s": tps},
        "records": {
            "train_tokens_per_s": tps, "steps": steps,
            "flops_per_token": fpt, "chips": len(devices),
            "batch": B, "seq": S, "cfg": cfg,
            "mesh_axes": list(mesh.axis_names),
            "mesh_shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "bus_bytes": comm_bytes.step_bus_bytes(cfg, B // dp, S, dp, tp),
            "wire_MB": wire_mb,
            "coll_axes": {r.path.rsplit("/", 1)[-1]: "+".join(r.axes)
                          for r in graph.records},
        },
        "checks": checks,
    }
