"""compressed_matmul_roofline: least time of the window's packed projections
over the device time of the ``compressed_matmul`` kernel's trace events.
Each forward in the window (one decode forward per step over the sequences
it decodes, one prefill forward per admission over its prompt) needs
2*rows*K*N FLOPs per packed matrix and one read of the packed weights'
payload and scales (``weight_stats``) plus its activations; its least time
is the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth."""
from collections import Counter

import costs


def read(ctx):
    ns = (ctx.trace or {}).get("kernels", {}).get("compressed_matmul")
    if not ns or "payload_bytes" not in ctx.weight_stats:
        return None
    wbytes = (ctx.weight_stats["payload_bytes"]
              + ctx.weight_stats["scale_bytes"])
    rows = Counter()                   # decode rows per step
    least = 0.0
    for n, i, t in ctx.window_tokens():
        if i == 0:
            least += costs.least_time(
                *costs.compressed_matmul_cost(ctx.model, n, wbytes), ctx.peaks)
        else:
            rows[t] += 1
    for r in rows.values():
        least += costs.least_time(
            *costs.compressed_matmul_cost(ctx.model, r, wbytes), ctx.peaks)
    ctx.notes.append(f"compressed_matmul: {ns * 1e-9:.6f} s of device time, "
                     f"least {least:.6f} s, {len(rows)} decode forwards")
    return 100.0 * least / (ns * 1e-9)
