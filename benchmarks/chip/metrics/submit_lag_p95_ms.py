"""Client (the benchmark's load generator): how late requests were
submitted, submit time minus due time on the harness clock, 95th
percentile over the requests due in the window.  Open loop only."""

import numpy as np


def read(rec):
    if rec["loop"] != "open":
        return None
    t0, t1 = rec["window"]
    lags = [(r.submit - r.due) * 1e3 for r in rec["requests"]
            if t0 <= r.due < t1 and r.submit is not None]
    return float(np.percentile(lags, 95)) if lags else None
