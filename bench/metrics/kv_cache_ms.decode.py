"""Device time per decode step of the step program's ops in the
``kv_cache`` named scope (containers and async ends left out), inside
the benchmark's ``bench.step`` spans, in milliseconds."""
import program_trace


def read(ctx):
    prog = program_trace.program(ctx)
    return prog.scope_ms_per_step("kv_cache") if prog else None
