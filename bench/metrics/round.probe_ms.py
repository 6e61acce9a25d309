"""round.probe_ms: own device time of the ops under the `round.probe`
named scope, per round."""


def read(ctx):
    if ctx.trace is None or ctx.rounds == 0:
        return None
    from bench.trace import scope_s
    t = scope_s(ctx.trace, "round.probe")
    return 1e3 * t / ctx.rounds if t > 0 else None
