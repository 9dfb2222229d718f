"""The ``sparse_rows`` kernel (interpret mode here) against float64 sums:
row layouts, the squares lane, empty tiles, entries already in order, and
padding."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sparse_rows import rows_accumulate


def _entries(rng, cap, n_src, n_out, ordered=False):
    src = rng.integers(0, n_src, cap).astype(np.int32)
    dst = (rng.zipf(1.3, cap) % n_out).astype(np.int32)
    if ordered:
        dst = np.sort(dst)
    vals = rng.integers(1, 5, cap).astype(np.float32)
    vals[cap // 3: cap // 3 + 10] = 0          # padding in the middle
    vals[-20:] = 0                             # and at the end
    dst[-20:] = 0
    return vals, src, dst


@pytest.mark.parametrize("row,squares,ordered,n_out,tc,slab", [
    ((2, 128), True, False, 1000, 128, 512),     # rows of aligned tiles
    ((1, 128), True, False, 3000, 128, 64),      # most tiles empty
    ((16,), False, False, 700, 64, 256),         # 2-D rows
    ((16,), False, True, 90, 8, 64),             # already ordered: no sort
])
def test_rows_accumulate_matches_float64(row, squares, ordered, n_out, tc,
                                         slab):
    rng = np.random.default_rng(len(row) + n_out)
    n_src, cap = 300, 5000
    vals, src, dst = _entries(rng, cap, n_src, n_out, ordered)
    M = rng.standard_normal((n_src, *row)).astype(np.float32)
    if squares:
        M[..., -1, -1] = 0                     # the lane the norms take
    want = np.zeros((n_out, *row))
    np.add.at(want, dst, np.einsum("j,j...->j...", vals.astype(np.float64),
                                   M[src].astype(np.float64)))
    if squares:
        want[..., -1, -1] = np.bincount(dst, weights=vals.astype(
            np.float64) ** 2, minlength=n_out)
    got = rows_accumulate(jnp.asarray(vals), jnp.asarray(src),
                          jnp.asarray(dst), jnp.asarray(M), n_out=n_out,
                          squares=squares, tc=tc, slab=slab)
    assert got.shape == (n_out, *row)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float64) - want).max() <= 1e-6 * scale
