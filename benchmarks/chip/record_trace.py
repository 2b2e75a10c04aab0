#!/usr/bin/env python3
"""Traced runs of one cell that keep more than ``run.py --trace 1`` does.

    python3 benchmarks/chip/record_trace.py --workload smollm-360m.chat \
        --seed 7 --seconds 51 --keep-ms 400 \
        --out benchmarks/chip/tests/v5e_chat_spans.json.gz
    python3 benchmarks/chip/record_trace.py --workload smollm-360m.chat \
        --seed 7 --seconds 51 --trace-seconds 51

The first is ``run.py``'s traced run, its line with ``serve`` added:
the five layer numbers of ``serve_spans.py`` and the share of idle device
seconds that ``idle_gaps`` labels with a ``serve.*`` span
(``serve_idle_share``), read from the traced span's events together with
the engine's ``serve.*`` spans, and that span's ``idle_gaps`` under
``serve_idle_gaps``.  With ``--out`` it also stores a trimmed, flattened
copy of those events as a test fixture: ``--keep-ms`` of them from 50 ms
before the first admission of the traced span, with ``bench.window`` cut
to that stretch.  The second traces the last ``--trace-seconds`` of the
window, the whole of it at 51, to measure what tracing costs: it reduces
nothing and reports the end-to-end metrics taken with the profiler on.
The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as R  # noqa: E402
import serve_spans as SP  # noqa: E402
import trace_reduce as TRC  # noqa: E402

LEAD_NS = 50e6


def trim(events: list, keep_ms: float) -> list:
    """``keep_ms`` of the events from ``LEAD_NS`` before the first
    ``serve.admit`` that leaves room for them inside ``bench.window`` (the
    window's start when there is none), ``bench.window`` cut to match."""
    win = next(e for e in events if e[2] == "bench.window")
    t0, t1 = win[3], win[3] + win[4]
    keep = keep_ms * 1e6
    admits = sorted(e[3] for e in events if e[2] == "serve.admit"
                    and t0 + LEAD_NS <= e[3] <= t1 - keep + LEAD_NS)
    a = admits[0] - LEAD_NS if admits else t0
    b = a + keep
    kept = [e for e in events
            if e[2] != "bench.window" and e[3] < b and e[3] + e[4] > a]
    return [[win[0], win[1], "bench.window", a, b - a]] + kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-ms", type=float, default=400.0)
    ap.add_argument("--out", help="where to write a trimmed trace")
    ap.add_argument("--trace-seconds", type=float,
                    help="trace this much of the window; reduce nothing")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)

    if args.trace_seconds is not None:
        R.TRACE_SECONDS = args.trace_seconds
        R.trace_record = lambda tracer, client: {
            "reduced": TRC.Reduced([], window=(0.0, 1.0)), "decode_steps": 0,
            "decode_live_rows": [], "prefill_prompts": []}
    else:
        record, serve = R.trace_record, {}

        def keep(tracer, client):
            path = TRC.find_xplane(str(tracer.dir))
            events = TRC.load(path) + SP.load(path)
            if args.out:
                with gzip.open(args.out, "wt") as fh:
                    json.dump(trim(events, args.keep_ms), fh)
            red = TRC.Reduced(events)
            rec = {"trace": {"reduced": red}}
            serve.update({k: f(rec) for k, f in SP.READERS.items()},
                         serve_idle_share=SP.serve_idle_share(red),
                         serve_idle_gaps=red.idle_gaps(10))
            return record(tracer, client)

        R.trace_record = keep
    try:
        out = R.run(cell, args.seed, args.seconds, True)
    except R.NoChip as e:
        R.log(f"[error] {e}")
        return 2
    line = R.result_line(cell, out, args.trace_seconds is None)
    line["e2e"] = {k: v for k, v in out["e2e"].items() if k != "samples"}
    line["trace_seconds"] = R.TRACE_SECONDS
    if args.trace_seconds is None:
        line["serve"] = serve
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
