"""Device programs executed per chunk fed in the window: how many
dispatches the streaming monoid's update takes for one chunk."""
import devtrace


def read(ctx):
    lo, hi = ctx.window
    plane = devtrace.first_plane(ctx.trace)
    chunks = ctx.counters.get("chunks", 0)
    if plane is None or not chunks:
        return None
    programs = ctx.trace.programs.get(plane, [])
    return sum(1 for _, start, _ in programs if lo <= start < hi) / chunks
