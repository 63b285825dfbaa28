"""Share of the window's wall that the ``train/step_chunk`` spans spent
before their wait on the device began: each chunk's duration less the
seconds its ``Span.sync`` blocked (``Telemetry.span_waits``), summed
over the window's chunks. With ``sync_loop_outside_chunk_pct`` it is
the host's share of the window. Host clock, program span; the first
chunk is set-up and is left out. A bus that keeps no waits (a program
from before it did) reads nothing."""


def read(ctx):
    li = ctx["inputs"]
    tele, spans = li.get("telemetry"), li.get("chunk_span_s")
    if not spans or not li.get("chunks") or not hasattr(tele, "span_waits"):
        return None
    waits = tele.span_waits("train/step_chunk")
    if len(waits) != len(spans):
        return None
    inside = slice(1, 1 + li["chunks"])
    enqueue = sum(spans[inside]) - sum(waits[inside])
    return 100.0 * enqueue / li["window_wall_s"]
