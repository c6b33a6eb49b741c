"""admit.prefill_ms: median, over the admissions that start in the traced
window (the program's ``engine.admit`` spans), of the host time of that
admission's prefill: the forward's dispatch (``engine.prefill``, a compile
included if one happens) and the pull of its cache (``kv.ingest.pull``,
which waits for the forward on the device)."""
import statistics

import program_spans


def read(ctx):
    win = program_spans.window(ctx, "admit.prefill_ms")
    if win is None:
        return None
    admits = win.named("engine.admit")
    if not admits:
        ctx.notes.append("admit.prefill_ms: no admission in the window")
        return None
    per = [program_spans.seconds(win.under(a, "engine.prefill")
                                 + win.under(a, "kv.ingest.pull"))
           for a in admits]
    ctx.notes.append(f"admit.prefill_ms: {len(admits)} admissions, prefill "
                     f"{[round(x * 1e3, 3) for x in per]} ms")
    return statistics.median(per) * 1e3
