"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Analytic artifacts (tables/figures
reproduced from the cost model) carry NaN timing; throughput rows time the
actual JAX/Pallas dividers on this host.

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--only SUBSTR[,SUBSTR]]
     [--json PATH]

``--json`` additionally writes every emitted row to a machine-readable JSON
file (section, name, us_per_call, derived) — CI uploads the
``BENCH_PR2.json`` / ``BENCH_PR3.json`` / ``BENCH_PR4.json`` /
``BENCH_PR5.json`` / ``BENCH_PR6.json`` / ``BENCH_PR7.json`` /
``BENCH_PR9.json`` / ``BENCH_PR10.json`` workflow artifacts from it.  ``--only`` filters sections by
case-insensitive title substring (comma-separated alternatives) and
overrides ``--quick``'s timed-section skip for the sections it selects.
"""

from __future__ import annotations

import argparse
import json
import math
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the timed throughput sections")
    ap.add_argument("--only", default="",
                    help="run only sections whose title contains one of "
                         "these comma-separated substrings")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write rows to a machine-readable JSON file")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import bench_tables as B

    # (title, fn, timed): timed sections are skipped under --quick.
    all_sections = [
        ("Table II (iterations/latency)", B.table2_rows, False),
        ("Table III (termination/rounding examples)", B.table3_rows, False),
        ("Figs 4-9 (synthesis cost model)", B.figs_synthesis_rows, False),
        ("Section IV deltas vs prior work [14]", B.prior_work_rows, False),
        ("Table II in compiled HLO (flops/division)",
         B.divider_hlo_flops_rows, False),
        ("Beyond-paper: radix-16 overlapped design point",
         B.radix16_rows, False),
        ("Static analysis (datapath proof margins + lint)",
         B.static_analysis_rows, False),
        ("Rowwise vs broadcast fused division",
         B.rowwise_vs_broadcast_rows, True),
        ("Flash bwd (fused recompute kernels vs float reference)",
         B.flash_bwd_rows, True),
        ("Decode throughput (static batch vs continuous scheduler)",
         B.decode_throughput_rows, True),
        ("Paged KV (dense vs paged cache, prefix sharing)",
         B.paged_kv_rows, True),
        ("Packed prefill (bucketed AOT admission vs per-request)",
         B.packed_prefill_rows, True),
        ("Serve SLO (TTFT/latency percentiles, fault isolation)",
         B.serve_slo_rows, True),
        ("Sharded serving (tensor-parallel decode + replica router)",
         B.sharded_serving_rows, True),
        ("Train step under the fused backend", B.train_step_fused_rows, True),
        ("Fused vs chained posit-division path",
         B.fused_vs_chained_rows, True),
        ("Multiword residual datapath (posit64 fused vs emulate)",
         B.multiword_rows, True),
        ("Posit64 wide-datapath divider", B.posit64_throughput_rows, True),
        ("Divider throughput (this host)", B.divider_throughput_rows, True),
    ]
    if args.only:
        keys = [k.strip().lower() for k in args.only.split(",") if k.strip()]
        sections = [(t, f) for t, f, _ in all_sections
                    if any(k in t.lower() for k in keys)]
        if not sections:
            titles = [t for t, _, _ in all_sections]
            print(f"--only {args.only!r} matched no section; have {titles}",
                  file=sys.stderr)
            sys.exit(2)
    else:
        sections = [(t, f) for t, f, timed in all_sections
                    if not (args.quick and timed)]

    print("name,us_per_call,derived")
    ok = True
    json_rows = []
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            for name, us, derived in fn():
                print(f'{name},{us:.3f},"{derived}"')
                json_rows.append({
                    "section": title, "name": name,
                    "us_per_call": None if math.isnan(us) else us,
                    "derived": derived,
                })
                if "match" in derived and "False" in derived:
                    ok = False
        except Exception as e:  # noqa: BLE001
            ok = False
            print(f'{title},nan,"ERROR: {type(e).__name__}: {e}"')
            json_rows.append({"section": title, "name": "ERROR",
                              "us_per_call": None,
                              "derived": f"{type(e).__name__}: {e}"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"ok": ok, "rows": json_rows}, f, indent=2)
        print(f"# wrote {len(json_rows)} rows to {args.json}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
