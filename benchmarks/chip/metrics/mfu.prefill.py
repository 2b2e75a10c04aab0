"""Model step, prefill (the engine's prefill executables): the FLOPs of
the real prompt tokens admitted in the traced span over the prefill
executions' device time times the chip's bf16 peak, in %."""

import trace_reduce as TRC
import work


def read(rec):
    st = TRC.record_steps(rec)
    prompts = rec["trace"]["prefill_prompts"] if st else []
    if not st or not st["prefill"] or not prompts:
        return None
    t = TRC.seconds_of(rec["trace"]["reduced"], st["prefill"])
    flops = sum(work.prefill_flops(rec["model"], n) for n in prompts)
    return 100.0 * flops / (t * rec["peaks"]["bf16_flops_per_s"])
