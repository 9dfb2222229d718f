"""The sparse summary pass's share of its roofline: the least time the chip
could take for the window's counted sparse work (its real nonzeros, once as
A and once as B: bench/work/sparse_summary_chunk.py) at the peaks of
bench/peaks.json, over the device's busy time in the window. f32 work is
held to the bf16 peak, the only one published. None where the cell counts
no nonzeros."""
import devtrace
import loader


def read(ctx):
    nnz = ctx.counters.get("nnz", 0)
    lo, hi = ctx.window
    busy = devtrace.device_busy_s(ctx.trace, lo, hi)
    if not nnz or busy <= 0:
        return None
    sz = ctx.cell.config["sizes"]
    work = loader.load_module("work", "sparse_summary_chunk")
    least = max(work.flops(nnz, nnz, sz["k"], sz["probes"])
                / ctx.peaks["bf16_flops_per_s"],
                work.bytes_moved(nnz, nnz) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
