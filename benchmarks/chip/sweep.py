#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same mix at several offered rates
on one engine, lowest first, to find the highest rate the program
sustains.

    python3 benchmarks/chip/sweep.py --workload smollm-360m.chat \\
        --rates 2,2.4,2.8 --seconds 30 --seed 5

A rate is sustained when, over its whole window:

- the backlog (requests due and not yet given a first token), read every
  quarter second, does not grow: its least-squares slope adds fewer than
  max(2, 5% of the arrivals) across the window;
- the tail does not rise: TTFT p95 of the window's second half is at most
  1.5 x that of its first half;
- requests do not queue for a slot: TTFT p50 is at most 1.5 x that of the
  lowest rate swept, which has to be well below the knee;
- delivered tokens track the offered load: tokens/s is at least 95% of
  the output tokens of the requests due in the window, per second.

One JSON line per rate on standard output; the last line names the knee
(the highest rate that is sustained with every lower one).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402

GRID_S = 0.25


def backlog(client, t):
    return sum(1 for r in client.recs
               if r.due <= t and not (r.times and r.times[0] <= t))


def halves_p95(client):
    """TTFT p95 (ms) of the requests due in each half of the window."""
    t0, t1 = client.t0, client.t1
    mid = (t0 + t1) / 2
    out = []
    for a, b in ((t0, mid), (mid, t1)):
        ttft = [((r.times[0] if r.times and r.times[0] < t1 else t1) - r.due)
                * 1e3 for r in client.recs if a <= r.due < b]
        out.append(R.pct(ttft, 95))
    return out


def judge(client, planned, base_p50):
    """The whole-window readings of one rate and whether it is sustained."""
    t0, t1 = client.t0, client.t1
    grid = np.arange(t0, t1, GRID_S)
    b = np.array([backlog(client, t) for t in grid], np.float64)
    slope = float(np.polyfit(grid - t0, b, 1)[0])
    growth = slope * (t1 - t0)
    due = [p for p in planned if 0.0 <= p.due < t1 - t0]
    offered = sum(p.max_new for p in due) / (t1 - t0)
    e2e = R.end_to_end(client)
    first, second = halves_p95(client)
    base = e2e["ttft_p50_ms"] if base_p50 is None else base_p50
    tests = {
        "backlog_flat": growth < max(2.0, 0.05 * len(due)),
        "tail_flat": second <= 1.5 * first,
        "no_slot_queue": e2e["ttft_p50_ms"] <= 1.5 * base,
        "tokens_track": e2e["tokens_per_s"] >= 0.95 * offered,
    }
    return {"arrivals": len(due), "backlog_mean": float(b.mean()),
            "backlog_growth": growth, "ttft_p95_halves_ms": [first, second],
            "offered_tokens_per_s": offered, **e2e, **tests,
            "sustained": all(tests.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    if cell["mix"]["loop"] != "open":
        raise SystemExit("a knee sweep needs an open-loop mix")
    try:
        ctx = R.prepare(cell, args.seed)
    except R.NoChip as e:
        R.log(f"[error] {e}")
        return 2
    rates = sorted(float(x) for x in args.rates.split(","))
    base_p50, knee = None, None
    for i, rate in enumerate(rates):
        mix = dict(cell["mix"], rate_rps=rate)
        client = R.Client(ctx["eng"], mix, args.seed + i, args.seconds,
                          ctx["conf"]["model"]["vocab"])
        client.run()
        out = judge(client, client.plan, base_p50)
        client.drain()
        if base_p50 is None:
            base_p50 = out["ttft_p50_ms"]
        if out["sustained"] and knee == (rates[i - 1] if i else None):
            knee = rate
        print(json.dumps({"rate_rps": rate, **out}), flush=True)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
