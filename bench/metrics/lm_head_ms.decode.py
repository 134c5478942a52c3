"""Device time per decode step of the step program's ops in the
``lm_head`` named scope (containers and async ends left out), inside
the benchmark's ``bench.step`` spans, in milliseconds."""
import program_trace


def read(ctx):
    prog = program_trace.program(ctx)
    return prog.scope_ms_per_step("lm_head") if prog else None
