"""Admission time per admitted request: the summed ``serve.admit`` spans
of the traced window (prefill call, first-token sampling, slot writes)
over the requests they admitted (each span's ``n_reqs``), in
milliseconds."""
import program_trace


def read(ctx):
    prog = program_trace.program(ctx)
    admits = prog.spans("serve.admit") if prog else []
    n = sum(int(a.get("n_reqs", 0)) for _, _, a in admits)
    if not n:
        return None
    return sum(d for _, d, _ in admits) / n / 1e6
