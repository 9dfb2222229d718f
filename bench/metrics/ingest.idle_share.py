"""Share of the ingest window in which no operation ran on the device."""
import devtrace


def read(ctx):
    return devtrace.idle_percent(ctx.trace, *ctx.window)
