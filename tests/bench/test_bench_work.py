"""Operation and byte counts (bench/work)."""
import pytest

import benchtiny  # noqa: F401
import loader


def test_summary_chunk_counts_at_the_ingest_shape():
    work = loader.load_module("work", "summary_chunk")
    rows, n, k, p = 16384, 4096, 512, 16
    sketch = 2 * k * rows * 2 * n                     # Pi A and Pi B
    assert sketch == 137438953472
    assert work.flops(rows, n, n, k, p) == sketch + 2 * rows * 2 * n \
        + 2 * 2 * rows * n * p
    assert work.bytes_moved(rows, n, n, k, p) == 4 * rows * 2 * n
    # the flop bound sets the least time on a v5e: about 0.72 ms
    least = work.flops(rows, n, n, k, p) / 197e12
    assert least == pytest.approx(0.72e-3, rel=0.01)
    assert least > work.bytes_moved(rows, n, n, k, p) / 819e9
