"""Device time of the MLPs (scope ``lm.mlp``) per tick, eval and retrain,
forward and backward; None in a program without the scope."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "ticks", "lm.mlp")
