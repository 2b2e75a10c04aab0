"""Kernels (kernels/posit_fused_div.py, the rowwise SRT divide behind
RMSNorm): its share of the roofline inside prefill, in %. HBM bounds it:
the bytes the divides of the traced span's real prompt tokens need, as
the cell's block counts them (f32 numerator in, f32 quotient out, one
f32 divisor per row), over the rowwise kernel's device time inside the
prefill executions times the chip's HBM bandwidth."""

import blocks
import trace_reduce as TRC


def _rowwise(op):
    name, code = TRC.op_name(op[2])
    return code == "custom-call" and "div_rowwise" in name


def read(rec):
    st = TRC.record_steps(rec)
    prompts = rec["trace"]["prefill_prompts"] if st else []
    if not st or not st["prefill"] or not prompts:
        return None
    t = TRC.seconds_of(rec["trace"]["reduced"], st["prefill"], _rowwise)
    if t <= 0:
        return None
    count = blocks.get(rec["block"]).rmsnorm_divide_bytes
    need = sum(count(rec["model"], n) for n in prompts)
    return 100.0 * need / (t * rec["peaks"]["hbm_bytes_per_s"])
