"""Host time per engine step: each ``serve.step`` span (one
``ServeEngine.step``) less its ``serve.step.wait`` child, the read-back in
which the host only waits for the device; the mean over the steps that
start in the traced window, in milliseconds."""
import program_trace


def read(ctx):
    prog = program_trace.program(ctx)
    steps = prog.spans("serve.step") if prog else []
    if not steps:
        return None
    waits = prog.children("serve.step", "serve.step.wait")
    host = [d - sum(w[1] for w in ws) for (_, d, _), ws in zip(steps, waits)]
    return sum(host) / len(host) / 1e6
