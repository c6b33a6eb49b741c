"""decode_mfu: the whole step's share of the chip's bf16 peak.  Model FLOPs
of every token served in the traced window (a decoded token at its context,
a first token as the prefill of its prompt; ``costs``) over the traced
window's seconds and the peak."""
import costs


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    flops = sum(costs.prefill_flops(ctx.model, n) if i == 0 else
                costs.decode_token_flops(ctx.model, n + i)
                for n, i, _ in ctx.window_tokens())
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace["window_s"]
                            * ctx.peaks["bf16_flops_per_s"])
