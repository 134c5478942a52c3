"""Device busy time inside the benchmark's ``bench.step`` spans (one
``ServeEngine.step`` each), per step, in milliseconds."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    steps = ctx.trace.spans("bench.step")
    if not steps:
        return None
    return ctx.trace.busy_s(steps) / len(steps) * 1e3
