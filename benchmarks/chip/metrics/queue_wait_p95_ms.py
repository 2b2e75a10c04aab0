"""Scheduler (serve/engine.py): ServeResult.queue_wait_ms, from submit to
admission, 95th percentile over the requests that finished in the
window."""

import numpy as np


def read(rec):
    t0, t1 = rec["window"]
    waits = [r.queue_wait_ms for r in rec["requests"]
             if r.finished_at is not None and t0 <= r.finished_at < t1
             and r.queue_wait_ms is not None]
    return float(np.percentile(waits, 95)) if waits else None
