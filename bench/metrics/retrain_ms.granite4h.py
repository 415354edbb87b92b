"""Device time under the manage loop's ``manage.retrain`` scope, per
retrain."""


def read(ctx):
    if not ctx.counts["retrains"]:
        return None
    return 1e3 * ctx.scope_s("manage.retrain") / ctx.counts["retrains"]
