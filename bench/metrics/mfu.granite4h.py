"""The granite tick's model FLOPs over the chips' bf16 peak: 2·N per
evaluated token, 6·N per trained token and the causal attention scores
(``bench/flops_granite.py``), over the traced window."""


def read(ctx):
    return 100.0 * ctx.cell.window_flops() / (
        ctx.window_s * ctx.chips * ctx.peaks["bf16_flops"])
