"""Host CPU time per chunk of the window's ingest call, in ms: the thread
CPU time of its ``repro.ingest.stage`` and ``repro.ingest.update`` spans
over the chunks it launched (bench/program_spans.py). A thread CPU clock
that ticks every 10 ms makes each span's time 0 or a tick; over a
window's ~5,000 chunks the sum holds ~2,000 ticks. None where the program
records no spans, or the launches are not the window's chunks."""
import program_spans


def read(ctx):
    call = program_spans.last_call()
    if call is None:
        return None
    updates = call.children.get(program_spans.UPDATE, [])
    if not updates or len(updates) != ctx.counters.get("chunks"):
        return None
    stages = call.children.get(program_spans.STAGE, [])
    return sum(o.cpu_ns for o in updates + stages) / len(updates) / 1e6
