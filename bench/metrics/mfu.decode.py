"""The whole step's share of the chip's peak: operations of every token
processed in the traced window (prompt tokens at admission, each fed-back
output token at its decode step, the head wherever logits are due,
attention at the live context length), over window times peak."""
import work


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.trace.devices:
        return None
    win, cfg = ctx.win, ctx.cell.config
    lo, hi = win.t0, win.t_end
    flops = 0.0
    for r in win.recs:
        ts = r.times
        if not ts:
            continue
        later = [j for j in range(1, len(ts)) if lo <= ts[j] <= hi]
        flops += work.request_flops(cfg, len(r.job.prompt), len(ts),
                                    lo <= ts[0] <= hi, later)
    return 100.0 * flops / (ctx.trace.window_s() * ctx.peaks["bf16_flops"])
