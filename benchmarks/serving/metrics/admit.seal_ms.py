"""admit.seal_ms: median, over the admissions that start in the traced
window (the program's ``engine.admit`` spans), of the host time that
admission spends sealing its prompt's pages: the summed ``kv.seal`` spans
nested in it (requantize, APack encode, CRC; ``PagedKVCache._seal``)."""
import statistics

import program_spans


def read(ctx):
    win = program_spans.window(ctx, "admit.seal_ms")
    if win is None:
        return None
    admits = win.named("engine.admit")
    if not admits:
        ctx.notes.append("admit.seal_ms: no admission in the window")
        return None
    seals = [win.under(a, "kv.seal") for a in admits]
    per = [program_spans.seconds(s) for s in seals]
    ctx.notes.append(f"admit.seal_ms: {len(admits)} admissions, "
                     f"{[len(s) for s in seals]} seals, "
                     f"{[round(x * 1e3, 3) for x in per]} ms")
    return statistics.median(per) * 1e3
