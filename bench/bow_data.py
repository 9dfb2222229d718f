"""A bag-of-words corpus made on the device from ``--seed``: documents of a
fixed number of tokens, each token a word drawn from Zipf(s) over the
vocabulary's ranks, the ranks put on column ids by a permutation drawn from
the seed. A document's row holds each distinct word once, valued by its
count. The corpus keeps the source's shape (documents, vocabulary, tokens
per document); the generator, its exponent and the fixed length are this
benchmark's assumptions.

Chunks are ``(rows, cols, vals)`` triples of one capacity, padded with
``vals == 0``: within a chunk, documents in order and each document's words
by column id.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Corpus(NamedTuple):
    chunks: List[tuple]     # (rows, cols, vals) per chunk, on the device
    nnz: List[int]          # real nonzeros of each chunk
    capacity: int           # entries held per chunk, padding included


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    """Cumulative Zipf(exponent) probabilities of ranks 1..vocab."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return (np.cumsum(w) / w.sum()).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("docs", "tokens"))
def _chunk_entries(key, cdf, perm, *, docs: int, tokens: int):
    """One chunk's tokens as per-slot entries: (cols, counts, first), each
    (docs, tokens); ``first`` marks the one slot that holds a word."""
    u = jax.random.uniform(key, (docs, tokens))
    ranks = jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                        cdf.shape[0] - 1)
    cols = jnp.sort(perm[ranks], axis=1)
    first = jnp.concatenate([jnp.ones((docs, 1), bool),
                             cols[:, 1:] != cols[:, :-1]], axis=1)
    pos = jnp.arange(tokens, dtype=jnp.int32)
    starts = jnp.where(first, pos, tokens)
    # the next word's first slot: a reversed running minimum of the starts
    nxt = jax.lax.cummin(starts, axis=1, reverse=True)
    nxt = jnp.concatenate([nxt[:, 1:], jnp.full((docs, 1), tokens)], axis=1)
    return cols, (nxt - pos).astype(jnp.float32), first


@functools.partial(jax.jit, static_argnames=("capacity",))
def _compact(cols, counts, first, *, capacity: int):
    """The marked slots packed into ``capacity`` entries, in slot order."""
    docs, tokens = cols.shape
    keep = first.reshape(-1)
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, capacity)
    rows = jnp.repeat(jnp.arange(docs, dtype=jnp.int32), tokens)

    def pack(x):
        return jnp.zeros((capacity,), x.dtype).at[dest].set(x, mode="drop")

    return (pack(rows), pack(cols.reshape(-1).astype(jnp.int32)),
            pack(counts.reshape(-1)))


def make_corpus(key, *, docs: int, chunk_docs: int, vocab: int,
                tokens: int, exponent: float, multiple: int) -> Corpus:
    """``docs // chunk_docs`` chunks; the capacity is the largest chunk's
    nonzeros rounded up to ``multiple``."""
    if docs % chunk_docs:
        raise ValueError(f"{docs} documents do not split into chunks of "
                         f"{chunk_docs}")
    k_perm, k_tok = jax.random.split(jax.random.fold_in(key, 2))
    cdf = jnp.asarray(zipf_cdf(vocab, exponent))
    perm = jax.random.permutation(k_perm, vocab).astype(jnp.int32)
    slots = [_chunk_entries(jax.random.fold_in(k_tok, c), cdf, perm,
                            docs=chunk_docs, tokens=tokens)
             for c in range(docs // chunk_docs)]
    nnz = [int(n) for n in jax.device_get([jnp.sum(s[2]) for s in slots])]
    capacity = -(-max(nnz) // multiple) * multiple
    chunks = []
    while slots:
        chunks.append(_compact(*slots.pop(0), capacity=capacity))
    return Corpus(jax.block_until_ready(chunks), nnz, capacity)
