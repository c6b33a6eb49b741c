"""page_seal.encode_ms_per_page: host time of one page's APack encode, K
and V together: the summed ``kv.seal.encode`` spans that start in the
traced window (``PagedKVCache._pack``: two ``kernels/ref.encode`` calls
and the pulls of their planes, one span per page and layer) over their
count.  Admissions' seals and the decode path's alike."""
import program_spans


def read(ctx):
    win = program_spans.window(ctx, "page_seal.encode_ms_per_page")
    if win is None:
        return None
    enc = win.named("kv.seal.encode")
    if not enc:
        ctx.notes.append("page_seal.encode_ms_per_page: no page encoded in "
                         "the window")
        return None
    total = program_spans.seconds(enc)
    ctx.notes.append(f"page_seal.encode_ms_per_page: {len(enc)} encodes, "
                     f"{total:.3f} s in all")
    return total / len(enc) * 1e3
