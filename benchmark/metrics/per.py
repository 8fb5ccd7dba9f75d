"""Source kind `counter_per`: one of the program's counters over the
window, a statement or a round of it.

    {"kind": "counter_per", "name": "stage_cache_dropped", "per": "round"}

`per` is "statement" (the window's statement records) or "round" (the
`rounds` a `replay_rounds` window reports). A program that never counted
under that name, as one older than the counter does not, gives nothing to
read, and so does a window with nothing to divide by.
"""

from __future__ import annotations


def read_counter_per(spec, ctx):
    from greengage_tpu.runtime.logger import counters

    n = (len(ctx.window) if spec["per"] == "statement"
         else ctx.info.get("rounds", 0))
    if not n or spec["name"] not in counters.snapshot():
        return None
    return ctx.counters.get(spec["name"], 0) / n
