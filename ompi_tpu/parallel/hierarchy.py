"""Hierarchical (two-level) collectives — the HAN analog.

≙ ompi/mca/coll/han: split a collective into an intra-node stage and an
inter-node stage over sub-communicators (coll_han_allreduce.c:92,
coll_han_subcomms.c). On TPU the levels are mesh axes: `inner` rides ICI
within a slice, `outer` rides DCN between slices/hosts. The bandwidth shape
is the same as HAN's: reduce-scatter inner → allreduce outer on 1/n_inner of
the data → allgather inner, so the slow (DCN) hops carry only the scattered
fraction.

On a single-slice mesh XLA would fuse a plain two-axis psum anyway; the
explicit staged form exists because on multi-slice meshes the outer allreduce
must move n_inner× less data over DCN — the entire point of HAN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import all_gather_invariant
from .mesh import classify_axes

# classify_axes is re-exported here as the PUBLIC topology-inference
# entry point: the traffic plane (traffic/planes.py) and auto_levels
# both key off the same ICI/DCN axis split, so there is exactly one
# implementation to pin in tests.
__all__ = ["classify_axes", "hierarchical_psum", "hierarchical_psum_quant",
           "hierarchical_allreduce", "auto_levels", "hier_axes",
           "hier_wire_bytes"]


def _pad_to_inner(x, inner: str):
    """Zero-pad dim 0 to a multiple of the inner axis size (exact for a
    sum — the pad rows reduce to zero and are sliced off after the
    allgather).  Returns (padded, original_len)."""
    ni = lax.psum(1, inner)        # static under shard_map
    orig = x.shape[0]
    pad = (-orig) % ni
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, orig


def hierarchical_psum(x, inner: str, outer: str):
    """For use inside shard_map: reduce-scatter over `inner`, psum over
    `outer`, allgather over `inner`.  Dim 0 of any length: non-divisible
    shapes (real gradient flats) are zero-padded to a multiple of the
    inner axis size and sliced back after the allgather."""
    x, orig = _pad_to_inner(x, inner)
    scattered = lax.psum_scatter(x, inner, scatter_dimension=0, tiled=True)
    reduced = lax.psum(scattered, outer)
    out = all_gather_invariant(reduced, inner, axis=0, tiled=True)
    return out[:orig] if out.shape[0] != orig else out


def hierarchical_psum_quant(x, inner: str, outer: str, n_outer: int,
                            block: int = None):
    """The `hier+quant` composition: same HAN shape, but the OUTER
    (DCN) allreduce rides the EQuARX block-quantized tier
    (coll/quant.psum_quant) while both inner (ICI) stages stay
    bitwise-native — the 2-rounding quantization error is paid only
    where the ~4x wire-byte cut buys wall-clock, on top of the
    n_inner× hierarchical reduction."""
    from ..coll.quant import psum_quant

    x, orig = _pad_to_inner(x, inner)
    scattered = lax.psum_scatter(x, inner, scatter_dimension=0, tiled=True)
    reduced = psum_quant(scattered, outer, n_outer, block=block)
    out = all_gather_invariant(reduced, inner, axis=0, tiled=True)
    return out[:orig] if out.shape[0] != orig else out


def hierarchical_allreduce(x: jax.Array, mesh: Mesh, inner: str, outer: str
                           ) -> jax.Array:
    """Standalone two-level allreduce over both axes of a mesh.

    x: (n_outer, n_inner, *elem) sharded over (outer, inner) — each (i, j)
    row is that rank's buffer; every row gets the global reduction.
    """
    spec = P(outer, inner)

    def local(xs):                    # (1, 1, *elem)
        flat = xs.reshape(xs.shape[2:])
        out = hierarchical_psum(flat, inner, outer)
        return out[None, None]

    from .. import traffic
    if traffic.enabled and not isinstance(x, jax.core.Tracer):
        # inner RS/AG rings + the outer ring on the scattered 1/n_inner
        # fraction — the per-plane rollup shows the HAN bandwidth shape
        ni = mesh.devices.shape[mesh.axis_names.index(inner)]
        no = mesh.devices.shape[mesh.axis_names.index(outer)]
        traffic.note_hierarchical(mesh, inner, outer,
                                  x.nbytes // max(ni * no, 1))

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                               out_specs=spec))
    return fn(x)


def auto_levels(mesh: Mesh):
    """Pick (inner, outer) from topology: ICI axes inner, DCN axes outer
    (classify_axes); falls back to (last, first) axis on flat meshes."""
    kinds = classify_axes(mesh)
    ici = [a for a, k in kinds.items() if k == "ici"]
    dcn = [a for a, k in kinds.items() if k == "dcn"]
    if ici and dcn:
        return ici[-1], dcn[0]
    names = list(mesh.axis_names)
    return names[-1], names[0]


def hier_axes(mesh: Mesh, axis):
    """Eligibility probe for the `hier` decision arm: given the axis (or
    axis tuple) a DeviceComm spans, return ``(inner, outer, None)`` when
    the comm is genuinely two-tier — at least one ICI level and one DCN
    level (classify_axes, including the ``topo_sim_dcn_axes`` override),
    both larger than 1 — else ``(None, None, why)`` where ``why`` is the
    human-readable ineligibility reason the decision audit records
    (``ineligible:hier:<why>``).  Unlike :func:`auto_levels` this never
    invents a split on a flat mesh: a single-plane comm has no slow tier
    to spare, so `hier` would only add stage latency."""
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    if len(axes) < 2:
        return None, None, "single-axis comm (no inner/outer levels)"
    kinds = classify_axes(mesh)
    dcn = [a for a in axes if kinds.get(a) == "dcn"]
    ici = [a for a in axes if kinds.get(a) == "ici"]
    if not dcn:
        return None, None, "single-plane mesh (no DCN axis among " \
            f"{axes})"
    if not ici:
        return None, None, "no ICI axis to scatter over (all of " \
            f"{axes} cross DCN)"
    inner, outer = ici[-1], dcn[0]
    if mesh.shape[inner] < 2:
        return None, None, f"degenerate inner level {inner!r} (size 1)"
    if mesh.shape[outer] < 2:
        return None, None, f"degenerate outer level {outer!r} (size 1)"
    return inner, outer, None


def hier_wire_bytes(count: int, dtype, ni: int, no: int,
                    quant: bool = False, block: int = None,
                    scale_dtype=None) -> dict:
    """Per-rank wire bytes of one hierarchical allreduce of ``count``
    elements: the HAN stage math — inner reduce-scatter and allgather
    each move (ni-1)/ni of the buffer over ICI, the outer allreduce
    moves 2(no-1)/no of the SCATTERED 1/ni fraction over DCN (the
    n_inner× slow-plane cut that is the algorithm's whole point).

    With ``quant`` the outer stage rides the EQuARX tier and its figure
    comes from coll/quant.wire_bytes (int8 payload + per-block scales);
    the inner stages stay native.  This is the single source of truth
    for the decision audit, the traffic plane's inner/outer split and
    the simulated-DCN delay shim — traffic conservation holds because
    all three read the same numbers.
    """
    import numpy as np

    esize = np.dtype(dtype).itemsize
    nbytes = int(count) * esize
    inner_stage = int((ni - 1) / ni * nbytes) if ni > 1 else 0
    outer_native = int(2 * (no - 1) / no * (nbytes // ni)) if no > 1 else 0
    outer = outer_native
    ratio = None
    if quant and no > 1:
        from ..coll.quant import wire_bytes as _qwire
        wb = _qwire("allreduce", max(int(count) // ni, 1), no, dtype,
                    block, scale_dtype)
        outer = wb["quant_bytes"]
        ratio = (outer / outer_native) if outer_native else None
    return {"inner_bytes": 2 * inner_stage,      # RS + AG stages
            "inner_stage_bytes": inner_stage,
            "outer_bytes": outer,
            "outer_native_bytes": outer_native,
            "total_bytes": 2 * inner_stage + outer,
            "ratio": ratio}
