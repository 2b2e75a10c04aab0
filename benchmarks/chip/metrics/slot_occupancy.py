"""Scheduler (serve/engine.py): active slot-steps over slot-steps of the
decode steps in the window, in %, from the engine's last_serve_stats
counters."""


def read(rec):
    c = rec["counters"]
    if not c["slot_steps"]:
        return None
    return 100.0 * c["active_slot_steps"] / c["slot_steps"]
