"""Traffic kind ``ingest_loop``: one tenant streams a pair through
``StreamingSummarizer.ingest`` in a closed loop.

Set-up makes ``pool`` chunk pairs on the device from the seed and warms the
update. The window feeds them in turn at advancing global row offsets (so
every chunk draws new projection rows) until ``--seconds`` have passed,
then waits for the state: ``ingest_rows_per_s`` is every row absorbed over
the whole window. The check rebuilds the summary of exactly the chunks fed
with the plain reference, on a sample of sketch columns drawn from the
seed, and compares every block and the row count.

Cell parameters (``traffic`` in ``bench/workloads/<cell>.json``):
``pool`` chunk pairs kept on the device and ``prefetch`` for ``ingest``.
Sizes and limits come from the configuration.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np

import data
import reference
from harness import Check, Outcome
from repro.core.streaming import StreamingSummarizer

#: sketch columns of A and of B that the reference rebuilds
CHECK_COLUMNS = 512


class Session(NamedTuple):
    cfg: dict
    traffic: dict
    seed: int
    key: jax.Array
    summarizer: StreamingSummarizer
    pool: tuple                  # (tuple of A chunks, tuple of B chunks)


def make_pool(cfg: dict, count: int, key):
    sz = cfg["sizes"]
    return jax.block_until_ready(data.make_pool(
        jax.random.fold_in(key, 1), count=count, rows=sz["chunk_rows"],
        n1=sz["n1"], n2=sz["n2"], decay=cfg["data"]["decay"],
        sigma=cfg["data"]["sigma"]))


def summarizer(cfg: dict) -> StreamingSummarizer:
    sz = cfg["sizes"]
    return StreamingSummarizer(k=sz["k"], probes=sz["probes"])


def setup(cell, seed: int) -> Session:
    cfg = cell.config
    key = data.seed_key(seed)
    pool = make_pool(cfg, cell.traffic["pool"], key)
    summ = summarizer(cfg)
    sz = cfg["sizes"]
    # warm the update and finalize at the chunk shape the window feeds
    state = summ.init(jax.random.fold_in(key, 0),
                      (sz["rows_declared"], sz["n1"], sz["n2"]))
    state = summ.update(state, pool[0][0], pool[1][0], 0)
    jax.block_until_ready(summ.finalize(state))
    return Session(cfg, cell.traffic, seed, key, summ, pool)


def chunk_plan(count: int, pool: int, rows: int) -> list:
    """(pool index, first global row) of each chunk fed, in order."""
    return [(c % pool, c * rows) for c in range(count)]


def window(session: Session, seconds: float, span) -> Outcome:
    sz = session.cfg["sizes"]
    rows = sz["chunk_rows"]
    pool_A, pool_B = session.pool
    summ = session.summarizer
    state = summ.init(jax.random.fold_in(session.key, 0),
                      (sz["rows_declared"], sz["n1"], sz["n2"]))
    jax.block_until_ready(state)
    fed = [0]

    def feed(t0: float):
        while time.perf_counter() - t0 < seconds:
            with span("bench.feed"):
                i = fed[0] % len(pool_A)
                fed[0] += 1
            yield pool_A[i], pool_B[i]

    with span("bench.window"):
        t0 = time.perf_counter()
        with span("bench.ingest"):
            state = summ.ingest(state, feed(t0), row_offset=0,
                                prefetch=session.traffic["prefetch"])
        with span("bench.block"):
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    chunks = fed[0]
    summary = jax.device_get(summ.finalize(state))
    rows_seen = int(state.rows_seen)
    del state
    return Outcome(
        end_to_end={"ingest_rows_per_s": chunks * rows / window_s},
        attempted=chunks, failed=0,
        counters={"chunks": chunks, "rows": chunks * rows,
                  "rows_seen": rows_seen, "window_s": window_s,
                  "summary": summary})


def check_columns(seed: int, n: int, salt: int) -> np.ndarray:
    """A sorted sample of ``CHECK_COLUMNS`` of ``n`` columns, drawn from the
    seed."""
    rng = np.random.default_rng([seed, salt])
    return np.sort(rng.choice(n, size=min(CHECK_COLUMNS, n), replace=False))


def program_blocks(summary, cols_A, cols_B) -> dict:
    return {"A_sketch": np.asarray(summary.A_sketch)[:, cols_A],
            "B_sketch": np.asarray(summary.B_sketch)[:, cols_B],
            "norm_A": np.asarray(summary.norm_A),
            "norm_B": np.asarray(summary.norm_B),
            "probes": np.asarray(summary.probes)}


def reference_blocks(session: Session, chunks: int, cols_A, cols_B,
                     passes: int) -> dict:
    sz = session.cfg["sizes"]
    pool_A, pool_B = session.pool
    return reference.stream_summary(
        jax.random.fold_in(session.key, 0), pool_A, pool_B,
        chunk_plan(chunks, len(pool_A), sz["chunk_rows"]), sz["k"],
        sz["probes"], cols_A, cols_B, passes)


def _readings(session: Session, outcome: Outcome, control: bool) -> dict:
    sz = session.cfg["sizes"]
    cols_A = check_columns(session.seed, sz["n1"], 1)
    cols_B = check_columns(session.seed, sz["n2"], 2)
    chunks = outcome.counters["chunks"]
    ref = reference_blocks(session, chunks, cols_A, cols_B, 6)
    if control:
        got = reference_blocks(session, chunks, cols_A, cols_B, 3)
        rows = got["rows"]
    else:
        got = program_blocks(outcome.counters["summary"], cols_A, cols_B)
        rows = outcome.counters["rows_seen"]
    return {"summary_gap": reference.summary_gap(got, ref),
            "rows_gap": float(abs(rows - ref["rows"]))}


def check(session: Session, outcome: Outcome) -> list:
    """The program's summary against the reference, with the limits."""
    limits = session.cfg["limits"]
    return [Check(name, value, limits[name]) for name, value in
            _readings(session, outcome, control=False).items()]


def control(session: Session, outcome: Outcome) -> dict:
    """The control's readings: the reference in bf16x3 in the program's
    place, against the reference in f32."""
    return _readings(session, outcome, control=True)
