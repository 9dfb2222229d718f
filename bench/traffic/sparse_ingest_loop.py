"""Traffic kind ``sparse_ingest_loop``: one tenant streams a bag-of-words
corpus through ``StreamingSummarizer.ingest`` in a closed loop, the same
sparse chunk as A and as B (the word co-occurrence product ``A^T A``).

Set-up makes the whole corpus on the device from the seed
(``bench/bow_data.py``) as ``SparseRows`` chunks of one capacity and warms
the update. The window feeds the chunks in order at advancing global row
offsets (pass ``p`` starts at row ``corpus_docs * p``, so every document
draws new projection rows) until ``--seconds`` have passed, then waits for
the state: ``ingest_rows_per_s`` is every document absorbed over the whole
window. The check rebuilds the summary of exactly the chunks fed with the
plain reference (``bench/reference_sparse.py``) at the checked sketch
columns, every norm and the whole probe block, and compares the row count.

Cell parameters (``traffic`` in ``bench/workloads/<cell>.json``):
``prefetch`` for ``ingest`` and ``capacity_multiple``, what the largest
chunk's nonzeros are rounded up to. Sizes and limits come from the
configuration.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax

import bow_data
import data
import reference
import reference_sparse
from harness import Check, Outcome
from repro.core.streaming import StreamingSummarizer
from repro.core.types import SparseRows


class Session(NamedTuple):
    cfg: dict
    traffic: dict
    seed: int
    key: jax.Array
    summarizer: StreamingSummarizer
    corpus: bow_data.Corpus
    pool: tuple                  # the corpus as SparseRows chunks


def make_corpus(cfg: dict, traffic: dict, key) -> bow_data.Corpus:
    sz = cfg["sizes"]
    return bow_data.make_corpus(
        jax.random.fold_in(key, 1), docs=sz["corpus_docs"],
        chunk_docs=sz["chunk_docs"], vocab=sz["n1"],
        tokens=sz["tokens_per_doc"], exponent=sz["zipf_exponent"],
        multiple=traffic["capacity_multiple"])


def init_state(session: "Session"):
    sz = session.cfg["sizes"]
    return session.summarizer.init(
        jax.random.fold_in(session.key, 0),
        (sz["rows_declared"], sz["n1"], sz["n2"]))


def setup(cell, seed: int) -> Session:
    cfg = cell.config
    sz = cfg["sizes"]
    if sz["n1"] != sz["n2"]:
        raise ValueError("one stream feeds both operands: n1 must be n2")
    key = data.seed_key(seed)
    corpus = make_corpus(cfg, cell.traffic, key)
    pool = tuple(SparseRows(*c, (sz["chunk_docs"], sz["n1"]))
                 for c in corpus.chunks)
    summ = StreamingSummarizer(k=sz["k"], probes=sz["probes"])
    session = Session(cfg, cell.traffic, seed, key, summ, corpus, pool)
    # warm the update and finalize at the chunk shape the window feeds
    state = summ.update(init_state(session), pool[0], pool[0], 0)
    jax.block_until_ready(summ.finalize(state))
    return session


def chunk_plan(count: int, pool: int, rows: int) -> list:
    """(pool index, first global row) of each chunk fed, in order."""
    return [(c % pool, c * rows) for c in range(count)]


def window(session: Session, seconds: float, span) -> Outcome:
    sz = session.cfg["sizes"]
    pool, summ = session.pool, session.summarizer
    state = jax.block_until_ready(init_state(session))
    fed = [0]

    def feed(t0: float):
        while time.perf_counter() - t0 < seconds:
            with span("bench.feed"):
                i = fed[0] % len(pool)
                fed[0] += 1
            yield pool[i], pool[i]

    with span("bench.window"):
        t0 = time.perf_counter()
        with span("bench.ingest"):
            state = summ.ingest(state, feed(t0), row_offset=0,
                                prefetch=session.traffic["prefetch"])
        with span("bench.block"):
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    chunks = fed[0]
    rows = chunks * sz["chunk_docs"]
    nnz = sum(session.corpus.nnz[i % len(pool)] for i in range(chunks))
    summary = jax.device_get(summ.finalize(state))
    rows_seen = int(state.rows_seen)
    del state
    return Outcome(
        end_to_end={"ingest_rows_per_s": rows / window_s},
        attempted=chunks, failed=0,
        counters={"chunks": chunks, "rows": rows, "nnz": nnz,
                  "rows_seen": rows_seen, "window_s": window_s,
                  "summary": summary})


def _readings(session: Session, outcome: Outcome, control: bool) -> dict:
    sz = session.cfg["sizes"]
    t, n = sz["chunk_docs"], sz["n1"]
    pool = reference_sparse.host_chunks(session.corpus.chunks)
    cols_A = reference_sparse.checked_columns(pool, n, session.seed, 1)
    cols_B = reference_sparse.checked_columns(pool, n, session.seed, 2)
    plan = chunk_plan(outcome.counters["chunks"], len(pool), t)
    key = jax.random.fold_in(session.key, 0)

    def ref(passes):
        return reference_sparse.stream_summary(
            key, pool, pool, plan, t, (n, n), sz["k"], sz["probes"],
            cols_A, cols_B, passes)

    want = ref(6)
    if control:
        got = ref(3)
        rows = got["rows"]
    else:
        s = outcome.counters["summary"]
        got = {"A_sketch": s.A_sketch[:, cols_A],
               "B_sketch": s.B_sketch[:, cols_B], "norm_A": s.norm_A,
               "norm_B": s.norm_B, "probes": s.probes}
        rows = outcome.counters["rows_seen"]
    return {"summary_gap": reference.summary_gap(got, want),
            "rows_gap": float(abs(rows - want["rows"]))}


def check(session: Session, outcome: Outcome) -> list:
    """The program's summary against the reference, with the limits."""
    limits = session.cfg["limits"]
    return [Check(name, value, limits[name]) for name, value in
            _readings(session, outcome, control=False).items()]


def control(session: Session, outcome: Outcome) -> dict:
    """The control's readings: the reference with its sketch in bf16x3 in
    the program's place, against the reference."""
    return _readings(session, outcome, control=True)
