"""The ``sparse_rows`` kernel (interpret mode here) against float64 sums:
row layouts, the squares lane, empty tiles, entries already in order, and
padding; the grouped entry loop's edges; bit parity with the per-entry loop."""
from pathlib import Path

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


def _float64_rows(vals, src, dst, M, n_out, squares):
    want = np.zeros((n_out, *M.shape[1:]))
    np.add.at(want, dst, np.einsum("j,j...->j...", vals.astype(np.float64),
                                   M[src].astype(np.float64)))
    if squares:     # the last element of each row, in either layout
        want.reshape(n_out, -1)[:, -1] = np.bincount(
            dst, weights=vals.astype(np.float64) ** 2, minlength=n_out)
    return want


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
    want = _float64_rows(vals, src, dst, M, n_out, squares)
    got = rows_accumulate(jnp.asarray(vals), jnp.asarray(src),
                          jnp.asarray(dst), jnp.asarray(M), n_out=n_out,
                          squares=squares, tc=tc, slab=slab)
    assert got.shape == (n_out, *row)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float64) - want).max() <= 1e-6 * scale


@pytest.mark.parametrize("row,squares,cap,n_out,tc,slab,run", [
    ((16,), False, 37, 200, 8, 8, 0),        # items shorter than PARTS
    ((16,), True, 203, 60, 8, 24, 0),        # heads and tails off PARTS
    ((1, 128), True, 203, 60, 8, 24, 50),    # a run of groups on one row
    ((2, 128), False, 301, 40, 16, 8, 100),  # the run across slabs of 8
    ((1, 128), False, 150, 30, 8, 24, 40),
    ((16,), True, 130, 30, 8, 8, 60),
])
def test_grouped_entries_match_float64(row, squares, cap, n_out, tc, slab,
                                       run):
    """Items that the loop walks in groups of ``PARTS`` entries and in
    single entries at their unaligned ends: short items, items off a
    multiple of ``PARTS``, one ``dst`` over several groups, slabs of 8 and
    24, entry counts that are not multiples of 8, both row layouts."""
    from repro.kernels.sparse_rows import PARTS, _by_dst, _items
    rng = np.random.default_rng(cap + run)
    src = rng.integers(0, 50, cap).astype(np.int32)
    dst = np.sort(rng.integers(0, n_out, cap)).astype(np.int32)
    dst[cap // 4: cap // 4 + run] = dst[cap // 4]       # one long run
    dst = rng.permutation(dst)
    vals = rng.standard_normal(cap).astype(np.float32)
    vals[::11] = 0
    M = rng.standard_normal((50, *row)).astype(np.float32)
    if squares:
        M.reshape(50, -1)[:, -1] = 0           # the lane the norms take
    pad = (-cap) % slab
    items = _items(_by_dst(*(jnp.asarray(np.pad(a, (0, pad))) for a in
                             (dst, src, vals)))[0], n_out, tc, slab)
    lo, hi = (np.asarray(a) for a in items[2:4])
    assert (lo % PARTS).any() and (hi % PARTS).any()
    assert ((hi - lo) < PARTS).any()
    if run:
        assert (hi - lo).max() >= 2 * PARTS or slab == PARTS
    got = rows_accumulate(jnp.asarray(vals), jnp.asarray(src),
                          jnp.asarray(dst), jnp.asarray(M), n_out=n_out,
                          squares=squares, tc=tc, slab=slab)
    want = _float64_rows(vals, src, dst, M, n_out, squares)
    assert np.abs(np.asarray(got, np.float64) - want).max() <= \
        1e-6 * np.abs(want).max()


PARENT = Path(__file__).with_name("sparse_rows_parent.npz")


@pytest.mark.parametrize("name,squares,n_out,tc,slab", [
    ("tiles", True, 40, 8, 24),     # (48, 1, 128) rows, 700 entries
    ("rows", True, 50, 16, 8),      # (64, 16) rows, 900 entries
])
def test_rows_accumulate_bit_identical_to_per_entry_loop(name, squares,
                                                         n_out, tc, slab):
    """The grouped loop adds every entry to the same partial, in the same
    order, with the same expression as the loop that updated one entry
    after another: its output, recorded in ``sparse_rows_parent.npz``
    with the inputs, is matched bit for bit."""
    with np.load(PARENT) as f:
        case = {k.split(".", 1)[1]: f[k] for k in f.files
                if k.startswith(name + ".")}
    got = rows_accumulate(*(jnp.asarray(case[k]) for k in
                            ("vals", "src", "dst", "M")), n_out=n_out,
                          squares=squares, tc=tc, slab=slab, interpret=True)
    assert (np.asarray(got) == case["out"]).all()
