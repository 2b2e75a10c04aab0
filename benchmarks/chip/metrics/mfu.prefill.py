"""Model step, prefill (the engine's prefill executables): the FLOPs of
the real prompt tokens admitted in the traced span, as the cell's block
counts them, over the prefill executions' device time times the chip's
bf16 peak, in %."""

import blocks
import trace_reduce as TRC


def read(rec):
    st = TRC.record_steps(rec)
    prompts = rec["trace"]["prefill_prompts"] if st else []
    if not st or not st["prefill"] or not prompts:
        return None
    t = TRC.seconds_of(rec["trace"]["reduced"], st["prefill"])
    count = blocks.get(rec["block"]).prefill_flops
    flops = sum(count(rec["model"], n) for n in prompts)
    return 100.0 * flops / (t * rec["peaks"]["bf16_flops_per_s"])
