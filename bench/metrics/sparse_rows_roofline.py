"""The ``sparse_rows`` kernel's share of its roofline: the least time the
chip could take for the window's counted sparse work
(bench/work/sparse_summary_chunk.py: the kernel's passes hold the sketches,
the norms and both halves of the probe summand) at the peaks of
bench/peaks.json, over the device time of the ``kernel:sparse_rows`` ops
in the window. None where the cell counts no nonzeros or the trace holds
no such kernel."""
import devtrace
import loader


def read(ctx):
    nnz = ctx.counters.get("nnz", 0)
    plane = devtrace.first_plane(ctx.trace)
    if not nnz or plane is None:
        return None
    lo, hi = ctx.window
    kernel_s = devtrace.time_by_name(ctx.trace.ops[plane], lo, hi).get(
        devtrace.KERNEL + "sparse_rows", 0.0)
    if kernel_s <= 0:
        return None
    sz = ctx.cell.config["sizes"]
    work = loader.load_module("work", "sparse_summary_chunk")
    least = max(work.flops(nnz, nnz, sz["k"], sz["probes"])
                / ctx.peaks["bf16_flops_per_s"],
                work.bytes_moved(nnz, nnz) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
