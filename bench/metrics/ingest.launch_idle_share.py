"""Share of the ingest window in which the device was idle while the host
was inside a ``repro.ingest.update`` span (launching a chunk's programs,
waiting at the runtime's in-flight limit included): the program's spans
put on the trace's clock (bench/program_spans.py), intersected with the
first chip's idle gaps, over the window. ``ingest.idle_share`` less this
is the idle time while the host was anywhere else."""
import devtrace
import program_spans


def read(ctx):
    call = program_spans.last_call()
    plane = devtrace.first_plane(ctx.trace)
    if call is None or plane is None:
        return None
    offset = program_spans.offset_ns(ctx.trace, call)
    if offset is None:
        return None
    launches = devtrace.union(
        [(o.name, o.start_ns + offset, o.end_ns - o.start_ns)
         for o in call.children.get(program_spans.UPDATE, [])])
    lo, hi = ctx.window
    idle = devtrace.idle_gaps(ctx.trace.ops[plane], lo, hi)
    return 100.0 * program_spans.overlap_ns(idle, launches) / (hi - lo)
