"""Readings that set the limits of ``correct`` for a training cell on a mesh
(``drivers/train_mesh.py``), run on the chip:

    python3 benchmark/controls_mesh.py --workload <name> --seeds 1,2 \
        [--readings control_float8,fault_half_batch]

For each seed it runs the float32 reference of
``references/olmo_mesh.py`` over the cell's chips and prints one JSON line
per reading, compared with it by ``drivers/train.py``'s ``compare``: the
control (the same reference with float8 matmuls, one precision below the
configuration's bfloat16, in the program's place) and the planted fault
that trains on half of each batch, read on the reference.  The fault that
returns the state unchanged reads 1 on ``grad_norm_gap`` and
``change_norm_gap`` by construction (no moment, no change).  The
benchmark's own runs never run this; ``benchmark/controls.py`` does the
same for the one-chip cells.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

READINGS = ("control_float8", "fault_half_batch")


def readings(cell, seeds, which, devices):
    """One dict per (seed, reading): the compared numbers and losses."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train as one_chip
    from benchmark.references import olmo_mesh
    from benchmark.run import seed32

    cfg = cell["config_data"]
    n = int(cell["check_steps"])
    out = []
    for s in seeds:
        key = jax.random.key(seed32(s))
        with jax.default_device(devices[0]):
            batches = one_chip.make_batches(cell, cfg, s,
                                            jax.random.fold_in(key, 1))[:n]
        base = olmo_mesh.run_reference(cfg, key, batches, devices, steps=n)
        kw = {"control_float8": {"mm_dtype": jnp.float8_e4m3fn},
              "fault_half_batch": {"rows": int(cell["batch"]) // 2}}
        for name in which:
            r = olmo_mesh.run_reference(cfg, key, batches, devices, steps=n,
                                        **kw[name])
            checks = one_chip.compare(r, base, cell["limits"])
            out.append({"cell": cell["name"], "reading": name, "seed": s,
                        "compared": {c["name"]: c["value"] for c in checks},
                        "losses": r["losses"], "ref_losses": base["losses"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--readings", default=",".join(READINGS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    which = [r for r in args.readings.split(",") if r]
    unknown = set(which) - set(READINGS)
    if unknown:
        ap.error(f"unknown readings {sorted(unknown)} (known: {READINGS})")

    from benchmark.run import load_cell

    cell = load_cell(args.workload)

    import jax

    from ompi_tpu.runtime import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("controls_mesh: needs a TPU with the cell's chips",
              file=sys.stderr)
        return 2
    for line in readings(cell, seeds, which, devices[:cell["chips"]]):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
