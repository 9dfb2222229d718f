"""Row accumulation of a sparse matrix's entries: ``out[dst[j]] += vals[j] *
M[src[j]]`` for every entry ``j`` — the sparse chunk's sketch ``P^T A``
(dst = column, src = row, M = the chunk's projection rows), its probe
summands, and with ``squares`` its squared column norms.

TPU design. ``M`` is copied into VMEM once per call and stays there (up to
``VMEM_LIMIT``, sized for a v5e's 128 MiB); the output is walked in tiles
of ``tc`` rows, each tile resident in VMEM while every entry that lands in
it is added on the VPU: an entry costs one row load, one multiply-add and
one row update in VMEM, and no (entries, m) block ever exists. Each output
row is held as ``PARTS`` partial sums, entry ``j`` adding to partial
``j % PARTS``, summed when the tile is done: a row that takes 15,000
entries (a frequent word) sums chains of ~1,900 f32 terms, not one of
15,000. The entries are walked in aligned groups of ``PARTS``, whose
entries add to distinct partials: a group loads all its rows of ``M`` and
of the partials before it stores any, so an entry does not wait on the
previous entry's store, and each partial still takes its entries in order
with the same arithmetic (the output is bit for bit that of one entry at a
time). An item's unaligned head and tail go one entry at a time.

The entries are ordered by ``dst`` first (one XLA sort, skipped when they
already are). The grid runs over *items*, the pieces into which the tile
starts and the fixed SMEM slabs of ``slab`` entries cut the ordered list,
so that every item lies in one slab and one tile. Scalar prefetch hands
each item its slab, tile and bounds, and whether it opens its tile (and
zeroes the partials) or closes it (and writes the block); an empty tile
still gets one empty item.

``M`` of shape ``(R, g, 128)`` keeps each row in one aligned (g, 128)
tile, so a row is one vector load; a 2-D ``(R, m)`` is read one sublane
row at a time. With ``squares``, ``vals[j]**2`` is also added at the last
element of the row, which the caller leaves free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: entries per SMEM slab (three int32/f32 vectors of this length)
SLAB = 8192
#: output rows per VMEM tile
TILE = 512
#: VMEM the kernel may use: M whole, the partials and two output blocks
VMEM_LIMIT = 100 * 2 ** 20
#: partial sums of each output row: entry j adds to partial j % PARTS
PARTS = 8


def _kernel(slab_ref, tile_ref, lo_ref, hi_ref, first_ref, last_ref,
            src_ref, dst_ref, val_ref, m_hbm, out_ref, m_vmem, acc_ref, sem,
            *, tc: int, squares: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        copy = pltpu.make_async_copy(m_hbm, m_vmem, sem)
        copy.start()
        copy.wait()

    @pl.when(first_ref[i] == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = tile_ref[i] * tc
    three_d = len(m_vmem.shape) == 3
    shape = m_vmem.shape[1:] if three_d else (1, m_vmem.shape[1])
    if squares:
        last = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == shape[0] - 1)
                & (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                   == shape[1] - 1)).astype(jnp.float32)

    def row(r):
        return m_vmem[r] if three_d else m_vmem[pl.ds(r, 1), :]

    def at(part, d):
        return (part, d) if three_d else (part, pl.ds(d, 1), slice(None))

    def update(v, m_row, acc_row):
        upd = v * m_row
        if squares:
            upd = upd + (v * v) * last
        return acc_row + upd

    def one(j, carry):
        r, d, v = src_ref[j], dst_ref[j] - base, val_ref[j]
        ix = at(j % PARTS, d)
        acc_ref[ix] = update(v, row(r), acc_ref[ix])
        return carry

    def group(g, carry):
        # entries g*PARTS + u add to partials u: distinct rows of acc, so
        # every load may come before every store
        js = [g * PARTS + u for u in range(PARTS)]
        rs = [src_ref[j] for j in js]
        ixs = [at(u, dst_ref[j] - base) for u, j in enumerate(js)]
        vs = [val_ref[j] for j in js]
        m_rows = [row(r) for r in rs]
        acc_rows = [acc_ref[ix] for ix in ixs]
        new = [update(*t) for t in zip(vs, m_rows, acc_rows)]
        for ix, a in zip(ixs, new):
            acc_ref[ix] = a
        return carry

    # whole groups between an unaligned head and tail of single entries
    lo, hi = lo_ref[i], hi_ref[i]
    g_lo = (lo + PARTS - 1) // PARTS
    g_hi = jnp.maximum(g_lo, hi // PARTS)
    jax.lax.fori_loop(lo, jnp.minimum(g_lo * PARTS, hi), one, 0)
    jax.lax.fori_loop(g_lo, g_hi, group, 0)
    jax.lax.fori_loop(g_hi * PARTS, hi, one, 0)

    @pl.when(last_ref[i] == 1)
    def _():
        out_ref[...] = jnp.sum(acc_ref[...], axis=0)


def _by_dst(dst, src, vals):
    """The entries ordered by ``dst``. Entries already in that order (a
    chunk's rows in row order, say) skip the sort: an entry of value 0 then
    takes the ``dst`` before it, which keeps the order and adds nothing."""
    kept = jax.lax.cummax(jnp.where(vals != 0, dst, 0))
    ordered = jnp.all((kept == dst) | (vals == 0))
    return jax.lax.cond(
        ordered, lambda e: (kept, e[1], e[2]),
        lambda e: jax.lax.sort(e, num_keys=1), (dst, src, vals))


def _items(dst, n_out: int, tc: int, slab: int):
    """(slab, tile, lo, hi, first, last) of each item of the ``dst``-sorted
    entries: the tile starts and the slab starts, merged, cut the list."""
    cap = dst.shape[0]
    n_tiles, n_slabs = -(-n_out // tc), cap // slab
    starts = jnp.searchsorted(dst, jnp.arange(n_tiles, dtype=jnp.int32) * tc,
                              side="left").astype(jnp.int32)
    pos = jnp.concatenate([starts, jnp.arange(n_slabs, dtype=jnp.int32)
                           * slab])
    is_tile = jnp.arange(n_tiles + n_slabs) < n_tiles
    # a tile's start goes before a slab's start at the same entry
    order = jnp.argsort(pos * 2 + (~is_tile).astype(jnp.int32))
    at, first = pos[order], is_tile[order]
    tile = jnp.where(first, order,
                     jnp.searchsorted(starts, at, side="right") - 1)
    end = jnp.concatenate([at[1:], jnp.full((1,), cap, jnp.int32)])
    which = jnp.minimum(at // slab, n_slabs - 1)
    closes = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    return (which.astype(jnp.int32), tile.astype(jnp.int32),
            (at - which * slab).astype(jnp.int32),
            (end - which * slab).astype(jnp.int32), first.astype(jnp.int32),
            closes.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_out", "squares", "tc",
                                             "slab", "interpret"))
def rows_accumulate(vals, src, dst, M, *, n_out: int, squares: bool = False,
                    tc: int = TILE, slab: int = SLAB,
                    interpret: bool | None = None):
    """``(n_out, *M.shape[1:])`` f32: ``out[dst[j]] += vals[j] * M[src[j]]``
    over the entries (and ``vals[j]**2`` at the last element of the row with
    ``squares``). Entries with ``vals == 0`` add nothing; ``dst`` must lie
    in ``[0, n_out)`` and ``src`` in ``[0, M.shape[0])``."""
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    slab = min(slab, max(8, vals.shape[0]))
    pad = (-vals.shape[0]) % slab
    if pad:     # padding entries have value 0 and add nothing
        vals, src, dst = (jnp.pad(a, (0, pad)) for a in (vals, src, dst))
    dst, src, vals = _by_dst(dst.astype(jnp.int32), src.astype(jnp.int32),
                             vals.astype(jnp.float32))
    items = _items(dst, n_out, tc, slab)
    n_tiles = -(-n_out // tc)
    row = M.shape[1:]
    zeros = (0,) * len(row)
    in_slab = pl.BlockSpec((slab,), lambda i, s, *_: (s[i],),
                           memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(items[0].shape[0],),
        in_specs=[in_slab, in_slab, in_slab,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tc, *row),
                               lambda i, s, t, *_: (t[i], *zeros)),
        scratch_shapes=[pltpu.VMEM(M.shape, jnp.float32),
                        pltpu.VMEM((PARTS, tc, *row), jnp.float32),
                        pltpu.SemaphoreType.DMA(())])
    out = pl.pallas_call(
        functools.partial(_kernel, tc=tc, squares=squares),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * tc, *row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT,
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sparse_rows",
    )(*items, src, dst, vals, M.astype(jnp.float32))
    return out[:n_out]
