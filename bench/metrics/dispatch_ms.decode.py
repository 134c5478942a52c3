"""Host time per engine step spent handing the step program to the
runtime: the ``serve.step.dispatch`` span (the jitted call until it
returns), the mean over the steps in the traced window, in
milliseconds."""
import program_trace


def read(ctx):
    prog = program_trace.program(ctx)
    calls = prog.spans("serve.step.dispatch") if prog else []
    if not calls:
        return None
    return sum(d for _, d, _ in calls) / len(calls) / 1e6
