"""Device time under the manage loop's ``manage.eval`` scope, per tick."""


def read(ctx):
    return 1e3 * ctx.scope_s("manage.eval") / ctx.counts["ticks"]
