"""admit_ms: median host time of one admission (prefill forward, page
ingest with its seals, device sync, first-token pick), from the harness's
span around the engine's per-slot prefill call, over the window's
admissions.  Nothing to read where the window admits nothing."""
import statistics


def read(ctx):
    spans = ctx.spans.within("admit", *ctx.window)
    if not spans:
        return None
    ctx.notes.append(f"admit_ms: {len(spans)} admissions in the window, "
                     f"{sum(spans):.3f} s in all")
    return statistics.median(spans) * 1e3
