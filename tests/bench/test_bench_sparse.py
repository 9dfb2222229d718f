"""The sparse bag-of-words cell (``nytbow.ingest``) at a size the CPU holds:
the loader finds it, its corpus has the shape the configuration states, a
sound run is correct, the control and three faults are not, and its work
count and roofline read known values."""
import json
import os

import numpy as np
import pytest

import benchtiny
import bow_data
import data
import harness
import loader
from repro.core import streaming
from repro.core.streaming import StreamingSummarizer

CELL = "nytbow.ingest"
TINY = {"n1": 1000, "n2": 1000, "k": 32, "probes": 4, "corpus_docs": 512,
        "chunk_docs": 128, "tokens_per_doc": 40}
SEED = 2 ** 33 + 5


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = benchtiny.tiny_root(tmp_path_factory.mktemp("root"))
    path = os.path.join(root, "bench", "configs", "bow-nytimes.json")
    with open(path) as f:
        doc = json.load(f)
    doc["sizes"].update(TINY)
    with open(path, "w") as f:
        json.dump(doc, f)
    path = os.path.join(root, "bench", "workloads", CELL + ".json")
    with open(path) as f:
        doc = json.load(f)
    doc["traffic"]["capacity_multiple"] = 1024
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def _traffic(root):
    cell = loader.load_cell(CELL, root)
    return cell, loader.load_module("traffic", cell.traffic["kind"])


def test_the_loader_finds_the_cell_and_its_metrics():
    cell = loader.load_cell(CELL, benchtiny.REPO)
    assert cell.chips == 1 and cell.config["reduced"] == []
    sz = cell.config["sizes"]
    assert (sz["n1"], sz["corpus_docs"], sz["chunk_docs"]) == \
        (102660, 300000, 15000)
    assert [m["name"] for m in cell.end_to_end] == ["ingest_rows_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "ingest.idle_share", "ingest.programs_per_chunk",
        "ingest.host_ms_per_chunk", "ingest.launch_idle_share",
        "bow.sparse_pass_roofline", "sparse_rows_roofline"}


def test_the_corpus_has_the_stated_shape():
    corpus = bow_data.make_corpus(data.seed_key(3), docs=256, chunk_docs=64,
                                  vocab=5000, tokens=333, exponent=1.0,
                                  multiple=4096)
    assert len(corpus.chunks) == 4 and corpus.capacity % 4096 == 0
    assert max(corpus.nnz) <= corpus.capacity
    rows, cols, vals = (np.asarray(a) for a in corpus.chunks[0])
    n = corpus.nnz[0]
    # every token counted once, every word of a document once, padding 0
    assert vals[:n].sum() == 64 * 333 and np.all(vals[:n] >= 1)
    assert not vals[n:].any()
    assert len(set(zip(rows[:n], cols[:n]))) == n
    assert np.all(np.diff(rows[:n]) >= 0)


def test_sound_runs_pass_and_the_control_fails(root, monkeypatch):
    import control
    monkeypatch.setattr(harness, "find_devices", benchtiny.fake_chip)
    limits = loader.load_cell(CELL, root).config["limits"]
    for row in control.readings(CELL, (5, SEED), 0.5, root=root):
        assert row["attempted"] >= 4        # a whole pass and more
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert row["control"]["summary_gap"] > limits["summary_gap"], row
        assert row["control"]["rows_gap"] <= limits["rows_gap"], row


def test_the_rate_takes_all_the_documents_and_all_the_window(root):
    cell, kind = _traffic(root)
    outcome = kind.window(kind.setup(cell, 3), 0.5, harness.span)
    c = outcome.counters
    assert c["window_s"] >= 0.5 and outcome.attempted == c["chunks"]
    assert outcome.end_to_end["ingest_rows_per_s"] == c["rows"] / \
        c["window_s"]
    assert c["rows"] == c["chunks"] * TINY["chunk_docs"] == c["rows_seen"]


def _correct(root, capsys) -> bool:
    rc = harness.run_cell(CELL, SEED, 0.5, False, root=root,
                          find=benchtiny.fake_chip)
    assert rc == 0
    return json.loads(capsys.readouterr().out.splitlines()[-1])["correct"]


def test_a_sound_run_is_correct(root, capsys):
    assert _correct(root, capsys)


def _dropped_nonzero(monkeypatch):
    absorb = StreamingSummarizer._absorb

    def drop(self, state, A, B, gids, t, hi1):
        A = A.__class__(A.rows, A.cols, A.vals.at[0].set(0), A.shape)
        return absorb(self, state, A, A, gids, t, hi1)

    monkeypatch.setattr(StreamingSummarizer, "_absorb", drop)


def _projection_in_bf16(monkeypatch):
    projection = streaming.projection_rows

    def bf16(key, gids, k, **kw):
        return projection(key, gids, k, **kw).astype("bfloat16").astype(
            "float32")

    monkeypatch.setattr(streaming, "projection_rows", bf16)
    streaming._sparse_contribution.clear_cache()


def _wrong_offset(monkeypatch):
    update = StreamingSummarizer.update

    def shifted(self, state, A, B, row_offset):
        return update(self, state, A, B, row_offset + 1)

    monkeypatch.setattr(StreamingSummarizer, "update", shifted)


@pytest.mark.parametrize("fault", [_dropped_nonzero, _projection_in_bf16,
                                   _wrong_offset])
def test_a_fault_under_the_timed_path_fails(root, capsys, monkeypatch,
                                            fault):
    fault(monkeypatch)
    try:
        assert not _correct(root, capsys)
    finally:
        monkeypatch.undo()
        streaming._sparse_contribution.clear_cache()


def test_the_work_count_at_the_cell_shape():
    work = loader.load_module("work", "sparse_summary_chunk")
    nnz, k, p = 3_500_000, 512, 16
    assert work.flops(nnz, nnz, k, p) == 2 * k * 2 * nnz + 2 * 2 * nnz \
        + 4 * nnz * p
    assert work.bytes_moved(nnz, nnz) == 16 * nnz
    # the bytes bound it on a v5e: about 68 us per chunk
    least = work.bytes_moved(nnz, nnz) / 819e9
    assert least == pytest.approx(68.4e-6, rel=0.01)
    assert least > work.flops(nnz, nnz, k, p) / 197e12


@pytest.mark.parametrize("metric,busy_ms", [
    ("bow.sparse_pass_roofline", 2.0),      # every op of the window
    ("sparse_rows_roofline", 1.0)])         # the kernel's ops alone
def test_the_rooflines_read_known_values(metric, busy_ms):
    import devtrace
    ops = {"/device:TPU:0": [("kernel:sparse_rows", 0, 1_000_000),
                             ("sort", 3_000_000, 1_000_000)]}
    trace = devtrace.Trace(ops, {}, [])
    cell = loader.load_cell(CELL, benchtiny.REPO)
    ctx = harness.MetricContext(cell, {"nnz": 1_000_000, "chunks": 1},
                                trace, (0, 4_000_000),
                                loader.load_peaks("TPU v5 lite"))
    reader = loader.load_module("metrics", metric)
    # 16 MB at 819 GB/s, over the busy time
    assert reader.read(ctx) == pytest.approx(100 * 16e6 / 819e9
                                             / (busy_ms * 1e-3))
    assert reader.read(ctx._replace(counters={"chunks": 1})) is None
