"""Serving launcher: drive a request stream against the continuous-batching
slot engine (or the static batch path with ``--static``).

``--stream`` consumes the engine's live event stream (tokens print as they
are produced); ``--deadline-ms`` / ``--max-queue`` / ``--max-queue-wait-ms``
exercise the robustness contract (requests past their budget finish
``DEADLINE``, overflow submissions ``SHED``) and the run ends with an SLO
summary: TTFT / per-token latency percentiles and the finish-reason mix.

``--packed-prefill`` admits queue-head prompts as ONE segment-masked
packed prefill per ``(bucket, pack-size)`` bin and ``--warmup``
AOT-compiles every bin's executable up front — together the A/B side of
per-request admission (outputs are bit-identical either way).

``--tp N`` shards each engine over an N-device ``("model",)`` mesh
(requires ``--tp-groups``, which also fixes the contraction-group
numerics so TP degrees stay bit-identical); ``--replicas R`` runs R such
engines on disjoint device subsets behind a :class:`ReplicaRouter`;
``--emit-async`` drains the event stream through the detokenize-thread
worker so printing never stalls decode."""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import (FinishEvent, ReplicaRouter, Request, ServeConfig,
                         ServeEngine, TokenEvent, stream_async)


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests in the stream (default 2x batch)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (ServeConfig.max_batch)")
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--posit-kv", type=str, default=None,
                    help="posit format for KV-cache quantization")
    ap.add_argument("--attn-backend", choices=["xla", "fused"], default="xla",
                    help="'fused' serves with posit division AND the fused "
                         "posit flash-attention kernel in chunked prefill "
                         "and per-slot decode")
    ap.add_argument("--static", action="store_true",
                    help="serve fixed batches to completion instead of the "
                         "continuous slot scheduler")
    ap.add_argument("--kv-layout", choices=["dense", "paged"],
                    default="dense",
                    help="'paged' serves attention KV from a refcounted "
                         "block pool with copy-on-write prefix sharing; "
                         "outputs are bit-identical to dense")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged layout; power of two "
                         "in [8, 128])")
    ap.add_argument("--packed-prefill", action="store_true",
                    help="admit queued prompts as ONE packed segment-masked "
                         "prefill per bucket (bit-identical to per-request "
                         "admission; A/B against the default solo path)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every admission bucket executable "
                         "before serving (warmup time is reported "
                         "separately and excluded from the serve timing)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="LEN",
                    help="prepend one shared LEN-token system prompt to "
                         "half the stream (exercises the prefix cache)")
    ap.add_argument("--stream", action="store_true",
                    help="consume the live event stream: submit every "
                         "request up front, print tokens as they arrive")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock budget from submission; "
                         "requests past it finish DEADLINE with their "
                         "partial output")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: submit() beyond it "
                         "sheds with a structured SHED result")
    ap.add_argument("--max-queue-wait-ms", type=float, default=None,
                    help="engine-wide queue-wait deadline (ms)")
    ap.add_argument("--strict", action="store_true",
                    help="legacy raising behavior: invalid requests and "
                         "overflow raise instead of shedding")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree per engine: shard params "
                         "and KV over a TP-device ('model',) mesh "
                         "(decoded tokens stay bit-identical to --tp 1 "
                         "for the same --tp-groups)")
    ap.add_argument("--tp-groups", type=int, default=0,
                    help="fixed contraction-group count for the sharded "
                         "head/ffn reductions (default: --tp when --tp>1); "
                         "set it to the LARGEST TP degree you compare "
                         "across so every degree is bit-identical")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas on disjoint device "
                         "subsets behind a least-loaded ReplicaRouter")
    ap.add_argument("--emit-async", action="store_true",
                    help="drain the event stream on a detokenize worker "
                         "thread behind a bounded backlog queue (decode "
                         "stepping decoupled from print/emit latency); "
                         "implies --stream")
    args = ap.parse_args()
    enable_compile_cache()

    # serving limits ride on the model config (get_config overrides), so no
    # ad hoc ServeConfig mutation here
    if args.emit_async:
        args.stream = True
    if args.static and (args.tp > 1 or args.replicas > 1):
        ap.error("--static serves one fixed-batch engine; use the "
                 "continuous scheduler with --tp/--replicas")
    cfg = get_config(args.arch, smoke=args.smoke,
                     fused=args.attn_backend == "fused",
                     max_batch=args.batch, max_seq=args.max_seq)
    if args.posit_kv:
        cfg = cfg.with_numerics(kv_cache_format=args.posit_kv)
    if args.tp > 1 or args.tp_groups:
        cfg = cfg.replace(tp_groups=args.tp_groups or args.tp)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    sc = ServeConfig.from_model(
        cfg, temperature=args.temperature,
        kv_layout=args.kv_layout,
        block_size=args.block_size,
        max_queue=args.max_queue,
        max_queue_wait_ms=args.max_queue_wait_ms,
        packed_prefill=args.packed_prefill,
        strict=args.strict)
    if args.tp > 1 or args.replicas > 1:
        from repro.launch.mesh import serve_meshes
        meshes = serve_meshes(args.tp, args.replicas)
        # tp == 1: each replica is unsharded and lives on its own device
        engines = [ServeEngine(cfg, params, sc, mesh=m) if args.tp > 1
                   else ServeEngine(cfg, params, sc,
                                    device=m.devices.flat[0])
                   for m in meshes]
        eng = ReplicaRouter(engines) if args.replicas > 1 else engines[0]
        print(f"# topology: tp={args.tp} x replicas={args.replicas} over "
              f"{args.tp * args.replicas}/{jax.device_count()} devices")
    else:
        eng = ServeEngine(cfg, params, sc)
    if args.warmup:
        t0 = time.perf_counter()
        census = eng.warmup(temperature=args.temperature or None)
        n_exec = (sum(sum(c.values()) for c in census)
                  if isinstance(census, list) else sum(census.values()))
        print(f"# warmup: {n_exec} executables compiled in "
              f"{time.perf_counter() - t0:.2f}s")

    # a mixed-length request stream: more requests than slots, ragged
    # prompts and budgets, so slots are freed and re-admitted mid-flight;
    # --shared-prefix makes half of them fork one system prompt, which the
    # paged layout serves from shared pages instead of re-prefilling
    n_req = args.requests or 2 * args.batch
    rng = np.random.default_rng(0)
    sys_p = (rng.integers(1, cfg.vocab,
                          size=args.shared_prefix).astype(np.int32)
             if args.shared_prefix else np.zeros(0, np.int32))
    reqs = []
    for i in range(n_req):
        p = rng.integers(1, cfg.vocab,
                         size=int(rng.integers(3, 12))).astype(np.int32)
        if args.shared_prefix and i % 2 == 0:
            p = np.concatenate([sys_p, p])
        reqs.append(Request(p, max_new=int(
            rng.integers(max(1, args.max_new // 2), args.max_new + 1)),
            deadline_ms=args.deadline_ms))

    t0 = time.perf_counter()
    results = {}
    if args.stream:
        for r in reqs:
            eng.submit(r)
        stream = (stream_async(eng) if args.emit_async
                  else eng.serve_stream())
        for ev in stream:
            if isinstance(ev, TokenEvent):
                print(f"req{ev.rid} += {ev.token}")
            elif isinstance(ev, FinishEvent):
                results[ev.rid] = ev.result
        outs = [results[i].tokens for i in sorted(results)]
    elif args.static:
        outs = eng.serve_static(reqs)
    else:
        outs = eng.serve(reqs)
        results = dict(enumerate(eng.last_results or []))
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    mode = ("stream" if args.stream
            else "static batches" if args.static else "continuous")
    print(f"# {mode}: {n_req} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, slots={args.batch}, "
          f"kv_layout={args.kv_layout})")
    st = eng.last_serve_stats
    if st and not args.static:
        ttft, lats = st["ttft_ms"], st["token_latency_ms"]
        reasons = dict(st["finish_reasons"])
        print(f"# slo: ttft_ms p50={_pct(ttft, 50):.1f} "
              f"p99={_pct(ttft, 99):.1f}  token_latency_ms "
              f"p50={_pct(lats, 50):.2f} p99={_pct(lats, 99):.2f}  "
              f"finish={reasons}  faults={st['faults']} "
              f"deadline={st['deadline_evictions']} shed={st['shed']}")
    if st and st.get("packed_prefill"):
        print(f"# packed: packs={st['packed_packs']} "
              f"segments={st['packed_segments']} "
              f"dummies={st['packed_dummies']}")
    if st and st.get("kv_layout") == "paged":
        print(f"# paged: block_size={st['block_size']} "
              f"peak_blocks={st['peak_blocks_in_use']}/{st['pool_blocks']} "
              f"prefix_hit_rate={st['prefix_hit_rate']:.0%} "
              f"({st['prefix_hit_tokens']}/{st['prompt_tokens']} prompt "
              f"tokens served from shared pages)")
    for i, o in enumerate(outs):
        tag = (f" [{results[i].finish.value}]"
               if i in results and results[i].detail else "")
        print(f"req{i}: prompt={reqs[i].tokens.tolist()} -> "
              f"{np.asarray(o).tolist()}{tag}")


if __name__ == "__main__":
    main()
