"""Paged decode attention kernel (ops/paged_attention, interpret mode).

The kernel reads each row's live pages in place; ``decode_attention``
over the dense gathered view of the same block table is the reference.
Rows of length 0 (an inactive slot, ``q_pos = -1``) read nothing and
come back zero.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu.models.transformer import decode_attention  # noqa: E402
from ompi_tpu.ops.paged_attention import paged_attention  # noqa: E402

PAGE, PMAX, HEADS, HD = 16, 4, 2, 128
FULL = PAGE * PMAX
# a dtype's tolerance against the reference, set from its rounding: the
# reference rounds its scores and output to the storage dtype
ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}

LENGTHS = {
    "edges": [0, 1, 15, 16, 17, FULL],
    "ragged": [FULL, 0, 33, 7, 0, 48, 2, 31],
    "one_row_full": [FULL],
}


def _problem(lengths, rows, dtype, seed=0, n_pages=40):
    """q, pools and a shuffled block table for ``lengths``: live entries
    point at distinct pages drawn from 1.., every other entry at the
    scratch page 0."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((rows, b, HEADS, HD)), dtype)
    kp = jnp.asarray(rng.standard_normal((rows, n_pages, PAGE, HEADS, HD)),
                     dtype)
    vp = jnp.asarray(rng.standard_normal((rows, n_pages, PAGE, HEADS, HD)),
                     dtype)
    bt = np.zeros((b, PMAX), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i, n in enumerate(lengths):
        need = -(-n // PAGE)
        bt[i, :need] = [free.pop() for _ in range(need)]
    return q, kp, vp, bt, np.asarray(lengths, np.int32)


def _reference(q, kp, vp, bt, lengths):
    r, b = q.shape[:2]
    k = jnp.take(kp, jnp.asarray(bt), axis=1).reshape(r, b, FULL, HEADS, HD)
    v = jnp.take(vp, jnp.asarray(bt), axis=1).reshape(r, b, FULL, HEADS, HD)
    return np.asarray(decode_attention(q, k, v, jnp.asarray(lengths - 1))
                      .astype(jnp.float32))


@pytest.mark.parametrize("group", [None, 2])
@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_kernel_matches_gathered_reference(case, dtype, rows, group):
    q, kp, vp, bt, lengths = _problem(LENGTHS[case], rows, dtype)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                          pages_per_group=group)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out.astype(jnp.float32))
    ref = _reference(q, kp, vp, bt, lengths)
    live = lengths > 0
    np.testing.assert_allclose(out[:, live], ref[:, live], rtol=0,
                               atol=ATOL[dtype])
    assert not np.any(out[:, ~live])          # inactive rows: zeros


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dead_pages_never_read(dtype):
    """NaN in every page no row reads (the scratch page included) and in
    every position past a row's length inside its last page: the output
    is finite and equal to that over clean pools."""
    lengths = LENGTHS["ragged"]
    q, kp, vp, bt, lens = _problem(lengths, 2, dtype)
    want = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                           pages_per_group=2)
    kn, vn = np.asarray(kp).copy(), np.asarray(vp).copy()
    dead = np.ones(kn.shape[1], bool)
    for i, n in enumerate(lengths):
        need = -(-n // PAGE)
        dead[bt[i, :need]] = False
        if n % PAGE:
            last = bt[i, need - 1]
            kn[:, last, n % PAGE:] = np.nan
            vn[:, last, n % PAGE:] = np.nan
    assert dead[0]
    kn[:, dead] = np.nan
    vn[:, dead] = np.nan
    got = paged_attention(q, jnp.asarray(kn, dtype), jnp.asarray(vn, dtype),
                          jnp.asarray(bt), jnp.asarray(lens),
                          pages_per_group=2)
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.asarray(want.astype(jnp.float32)))


def test_pool_dtype_must_match_q():
    q, kp, vp, bt, lens = _problem([5], 1, jnp.float32)
    with pytest.raises(ValueError, match="pools"):
        paged_attention(q, kp.astype(jnp.bfloat16), vp, jnp.asarray(bt),
                        jnp.asarray(lens))
