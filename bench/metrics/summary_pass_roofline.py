"""The summary pass's share of its roofline: the least time the chip could
take for the chunks' counted work (bench/work/summary_chunk.py) at the
peaks of bench/peaks.json, over the device's busy time in the window. f32
work is held to the bf16 peak, the only one published."""
import devtrace
import loader


def read(ctx):
    chunks = ctx.counters.get("chunks", 0)
    lo, hi = ctx.window
    busy = devtrace.device_busy_s(ctx.trace, lo, hi)
    if not chunks or busy <= 0:
        return None
    sz = ctx.cell.config["sizes"]
    work = loader.load_module("work", "summary_chunk")
    shape = (sz["chunk_rows"], sz["n1"], sz["n2"], sz["k"], sz["probes"])
    least = max(work.flops(*shape) / ctx.peaks["bf16_flops_per_s"],
                work.bytes_moved(*shape) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * chunks * least / busy
