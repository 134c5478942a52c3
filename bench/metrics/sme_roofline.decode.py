"""The SME kernels' share of their roofline in decode steps: the least
time the chip needs for the projections of the traced ``bench.step``
spans (every SME call of one decode step on all ``slots`` rows, as the
architecture's module counts them: ``sme_calls``), over the summed
device time of the SME kernel events inside those spans.

An SME kernel is a Pallas call (custom-call target ``tpu_custom_call``)
whose HLO name the program's backend wrappers give it: ``_v1_call``,
``_v2_call``, ``_v3_call``, ``_v3_decode_call``, numbered."""
import re

import work
from arch import for_config

_SME_CALL = re.compile(r"_v\d\w*_call(\.\d+)*$")


def is_kernel(op):
    return op[3] == "tpu_custom_call" and bool(_SME_CALL.match(op[0]))


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.trace.devices:
        return None
    steps = ctx.trace.spans("bench.step")
    ops = ctx.trace.ops_in(steps, is_kernel)
    if not ops:
        return None
    cfg, m = ctx.cell.config, ctx.cell.traffic["slots"]
    per_step = work.least_time(for_config(cfg).sme_calls(cfg, m), ctx.peaks)
    kernel_s = sum(op[2] for op in ops) / 1e9 / len(ctx.trace.devices)
    return 100.0 * per_step * len(steps) / kernel_s
