"""fused_page_attention_roofline: least time of the window's paged decode
attention over the device time of the ``fused_page_attention`` kernel's
trace events.  FLOPs and bytes: the architecture's ``page_attention_cost``
over every decoded token's cached keys, with the KV payload the window's
steps read (``kv_stats`` ``kv_read_bytes``, coded size of PACKED pages).
The least time is the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth, taken over the whole window."""
import costs


def read(ctx):
    ns = (ctx.trace or {}).get("kernels", {}).get("fused_page_attention")
    if not ns:
        return None
    contexts = [n + i - 1 for n, i, _ in ctx.window_tokens() if i > 0]
    kv_bytes = ctx.kv1["kv_read_bytes"] - ctx.kv0["kv_read_bytes"]
    flops, nbytes = costs.page_attention_cost(ctx.model, contexts, kv_bytes)
    least = costs.least_time(flops, nbytes, ctx.peaks)
    ctx.notes.append(f"fused_page_attention: {ns * 1e-9:.6f} s of device "
                     f"time, least {least:.6f} s ({flops} FLOPs, {nbytes} B)")
    return 100.0 * least / (ns * 1e-9)
