"""Client (the benchmark's load generator): time to first token, 95th
percentile, from the due time on the harness clock, over the requests due
in the window; a request with no token by the close counts its wait so
far.  The end-to-end ``ttft_p95_ms`` by the same rule, reported per layer
in open-loop cells whose tail a host stall of a second sets."""

import numpy as np


def read(rec):
    if rec["loop"] != "open":
        return None
    t0, t1 = rec["window"]
    ttft = [((r.times[0] if r.times and r.times[0] < t1 else t1) - r.due) * 1e3
            for r in rec["requests"] if t0 <= r.due < t1]
    return float(np.percentile(ttft, 95)) if ttft else None
