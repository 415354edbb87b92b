"""Device time of the attention mixers (scope ``lm.attn``: the QKV
projections, the scores and the output projection) per tick, eval and
retrain, forward and backward; None in a program without the scope."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "ticks", "lm.attn")
