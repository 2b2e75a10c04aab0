"""Model step, decode (the engine's decode executable): the FLOPs the
traced span's decode tokens need, as the cell's block counts them
(active slots, live KV rows), over the decode executions' device time
times the chip's bf16 peak, in %."""

import blocks
import trace_reduce as TRC


def read(rec):
    st = TRC.record_steps(rec)
    rows = rec["trace"]["decode_live_rows"] if st else []
    if not st or not st["decode"] or not rows:
        return None
    t = TRC.seconds_of(rec["trace"]["reduced"], st["decode"])
    return 100.0 * blocks.get(rec["block"]).decode_flops(rec["model"], rows) / (
        t * rec["peaks"]["bf16_flops_per_s"])
