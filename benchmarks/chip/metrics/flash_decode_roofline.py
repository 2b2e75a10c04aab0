"""Kernels (kernels/posit_flash_attn.py): decode attention's share of
its roofline, in %. HBM bounds it: the bytes the attention needs, as the
cell's block counts them (llama: live K/V rows in the cache's bf16 at
the model's head_dim, once per KV head, plus q and out), over the
attention kernel's device time inside the decode executions times the
chip's HBM bandwidth."""

import blocks
import trace_reduce as TRC


def read(rec):
    st = TRC.record_steps(rec)
    rows = rec["trace"]["decode_live_rows"] if st else []
    if not st or not st["decode"] or not rows:
        return None
    t = TRC.seconds_of(rec["trace"]["reduced"], st["decode"], TRC.is_attention)
    if t <= 0:
        return None
    return 100.0 * blocks.get(rec["block"]).flash_decode_bytes(rec["model"], rows) / (
        t * rec["peaks"]["hbm_bytes_per_s"])
