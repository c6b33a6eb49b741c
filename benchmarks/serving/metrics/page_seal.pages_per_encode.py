"""page_seal.pages_per_encode: pages packed per APack encode call in the
traced window, admissions and the decode path together: the change of
``kv_pages_packed`` over that of ``kv_seal_encode_calls`` (``kv_stats``
before and after the window; ``PagedKVCache._pack_pending`` encodes every
page one admission or step seals in a few batched calls).  Nothing where
the program has no such counter or encoded no page in the window."""


def read(ctx):
    if "kv_seal_encode_calls" not in ctx.kv1:
        ctx.notes.append("page_seal.pages_per_encode: the program counts "
                         "no encode calls")
        return None
    calls = ctx.kv1["kv_seal_encode_calls"] - ctx.kv0["kv_seal_encode_calls"]
    pages = ctx.kv1["kv_pages_packed"] - ctx.kv0["kv_pages_packed"]
    if calls == 0:
        ctx.notes.append("page_seal.pages_per_encode: no page encoded in "
                         "the window")
        return None
    ctx.notes.append(f"page_seal.pages_per_encode: {pages} pages in "
                     f"{calls} encode calls")
    return pages / calls
