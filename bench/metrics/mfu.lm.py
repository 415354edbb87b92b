"""The whole tick's model FLOPs over the chips' bf16 peak: 2·N per
evaluated token plus 6·N per trained token, over the traced window."""
from bench.flops import model_flops


def read(ctx):
    n = ctx.cell.model_params()
    c = ctx.counts
    flops = (model_flops(n, c["eval_tokens"], train=False)
             + model_flops(n, c["trained_tokens"], train=True))
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
