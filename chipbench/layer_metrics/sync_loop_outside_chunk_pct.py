"""Share of the window's wall that lies outside the program's
``train/step_chunk`` spans: the sync trainer's host loop between fused
chunks (records, hooks, gang and chaos checks). Host clock, program
span. The first chunk's span is set-up and is left out."""


def read(ctx):
    li = ctx["inputs"]
    spans = li.get("chunk_span_s")
    if not spans or not li.get("chunks"):
        return None
    inside = sum(spans[1:1 + li["chunks"]])
    return 100.0 * (1.0 - inside / li["window_wall_s"])
