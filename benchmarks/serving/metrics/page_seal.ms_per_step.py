"""page_seal.ms_per_step: host time per window step in the decode path's
page seals: requantizing each page that filled, its APack encode and CRC
(``PagedKVCache._seal``) and the push of sealed pages to the device
(``PagedKVCache._flush_device``), from the harness's spans around those two
calls.  The pull of a filled page, which waits for the step's device work,
is left out, as are the seals of admitted prompts (``admit_ms``)."""


def read(ctx):
    spans = ctx.spans.within("seal", *ctx.window, outside="admit")
    if not spans:
        return None
    pages = ctx.kv1["kv_pages_packed"] - ctx.kv0["kv_pages_packed"]
    ctx.notes.append(f"page_seal: {len(spans)} seal and push calls over "
                     f"{ctx.steps} steps; {pages} pages packed in the window, "
                     f"admissions included (kv_stats kv_pages_packed)")
    return sum(spans) / ctx.steps * 1e3
