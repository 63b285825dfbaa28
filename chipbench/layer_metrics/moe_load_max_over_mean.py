"""Imbalance of the experts held: rows of the most loaded (layer,
expert) over the mean rows of one, averaged over the window's steps,
from the counters the program's records carry (``moe_rows_max``,
``moe_rows_mean``; sown per layer by the expert layer). 1.0 is an even
load. Program counter."""


def read(ctx):
    li = ctx["inputs"]
    most, mean = li.get("moe_rows_max"), li.get("moe_rows_mean")
    if not most or not mean:
        return None
    return sum(a / b for a, b in zip(most, mean) if b) / len(most)
