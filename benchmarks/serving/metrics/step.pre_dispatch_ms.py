"""step.pre_dispatch_ms: median, over the steps that start in the traced
window (the program's ``engine.step`` spans) and dispatch a decode, of the
host time from the step's start to the start of its decode dispatch
(``engine.decode_dispatch``), less the step's admissions (``engine.admit``
spans): retirement, admission bookkeeping and the page-table metadata
(``kv.step_meta``) that the device waits behind in every step."""
import statistics

import program_spans


def read(ctx):
    win = program_spans.window(ctx, "step.pre_dispatch_ms")
    if win is None:
        return None
    per = []
    for st in win.named("engine.step"):
        dispatch = win.under(st, "engine.decode_dispatch")
        if dispatch:
            per.append(dispatch[0].t0 - st.t0 - program_spans.seconds(
                win.under(st, "engine.admit")))
    if not per:
        ctx.notes.append("step.pre_dispatch_ms: no decode dispatch in the "
                         "window")
        return None
    ctx.notes.append(f"step.pre_dispatch_ms: {len(per)} steps, "
                     f"{[round(x * 1e3, 3) for x in per]} ms")
    return statistics.median(per) * 1e3
