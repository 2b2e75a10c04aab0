#!/usr/bin/env python3
"""On-chip serving benchmark: one cell (a model configuration under a
traffic mix) per run.

    python3 benchmarks/chip/run.py --workload smollm-360m.chat --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration file and its traffic mix are looked up by name
in ``BENCHMARK.json``; per-layer metrics are the readers under
``metrics/``.  The configuration file names its block (``blocks/<name>.py``:
the architecture's weights, reference, work counts and division sites).
A run makes its weights and requests from ``--seed``, warms up every
prompt bucket the mix uses, runs the mix's lead-in, measures for
``--seconds``, and then checks what the window served against the block's
plain float32 reference.  The last line of standard output is one JSON
object; with ``--trace 1`` the metrics are the per-layer ones, read from
a profiler trace of part of the window.  Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import traffic as TR  # noqa: E402

TRACE_SECONDS = 6.0


class NoChip(RuntimeError):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cell


def load_cell(name: str) -> dict:
    """Everything a run needs about one cell, from ``BENCHMARK.json`` and
    the files it names."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    metrics = [m for m in bench["per_layer"]
               if name in m.get("workloads", [name])]
    return {
        "name": name,
        "chips": w["chips"],
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "mix": json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": metrics,
    }


def read_metric(name: str, rec: dict):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


# ------------------------------------------------------------------ device


def devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else the fixed ``.jax_cache/`` at the checkout's root."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


class CompileCounter:
    """Counts programs lowered while ``armed`` (each one is a trace and a
    compile or a cache load)."""

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if self.armed and name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


# ------------------------------------------------------------------ engine


def build_engine(conf: dict, params):
    """The program's serving engine for a configuration file."""
    import weights
    from repro.configs import get_config
    from repro.serve import ServeConfig, ServeEngine

    cfg = get_config(conf["arch"], fused=conf["fused"],
                     max_batch=conf["slots"], max_seq=conf["max_seq"])
    cfg = cfg.replace(**conf["model"])
    weights.check(conf, params, cfg)
    sc = ServeConfig.from_model(cfg, temperature=0.0, eos_id=-1)
    return ServeEngine(cfg, params, sc)


def warm_up(eng, buckets: List[int], vocab: int) -> None:
    """One request per prompt bucket, with a decode step after its prefill,
    so that every executable the window drives is compiled (or loaded)."""
    from repro.serve import Request

    for b in buckets:
        eng.submit(Request(np.ones(b, np.int32) % vocab, max_new=2))
        for _ in eng.serve_stream():
            pass


# ------------------------------------------------------------------ client


@dataclasses.dataclass
class Rec:
    """One request as the client saw it; times on the harness clock."""

    prompt: np.ndarray
    due: float
    submit: float = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    finished_at: Optional[float] = None
    queue_wait_ms: Optional[float] = None


class Client:
    """Drives ``ServeEngine.submit`` / ``serve_stream`` as the mix says and
    records every event on the harness clock.

    Open loop: each request is submitted when it falls due (or at the
    first point after that at which the stream hands control back, between
    two events).  Closed loop: each client submits its next request when
    its last one finished.  A drained engine ends its stream; the next
    submission starts a new session, whose request ids start again at 0.
    """

    def __init__(self, eng, mix: dict, seed: int, seconds: float, vocab: int):
        self.eng, self.mix = eng, mix
        self.recs: List[Rec] = []
        self.by_id: Dict[tuple, Rec] = {}
        self.session = -1
        self.stream = None
        self.stream_session = None
        self.done_counts = {"decode_steps": 0, "active_slot_steps": 0}
        self._finished_state = None
        self.t0 = None
        self.seconds = float(seconds)
        if mix["loop"] == "open":
            self.plan = TR.open_loop(mix, seed, seconds, vocab)
        else:
            self.source = TR.closed_loop(mix, seed, vocab)

    # engine counters summed over sessions (ServeEngine.last_serve_stats
    # for ended sessions, the live session's counters for the current one)
    def counters(self) -> dict:
        out = dict(self.done_counts)
        st = self.eng._st
        if st is not None and st is not self._finished_state:
            out["decode_steps"] += st.decode_steps
            out["active_slot_steps"] += st.active_slot_steps
        return out

    def _submit(self, r: TR.Planned, due: float, now: float) -> None:
        import jax

        from repro.serve import Request

        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.eng.submit(Request(r.prompt, max_new=r.max_new))
        if rid == 0:
            self.session += 1
        rec = Rec(r.prompt, due, submit=now)
        self.recs.append(rec)
        self.by_id[(self.session, rid)] = rec
        if self.stream is None:
            self.stream = self.eng.serve_stream()
            self.stream_session = self.session

    def _next(self):
        import jax

        from repro.serve import FinishEvent

        with jax.profiler.TraceAnnotation("bench.next"):
            ev = next(self.stream, None)
        now = time.perf_counter()
        if ev is None:
            stats = self.eng.last_serve_stats
            for k in self.done_counts:
                self.done_counts[k] += stats[k]
            self._finished_state = self.eng._st
            self.stream = None
            st = self.eng._st
            if st is not None and not st.drained:
                # a submission started a new session before this one ended
                self.stream = self.eng.serve_stream()
                self.stream_session = self.session
            return None, now
        rec = self.by_id[(self.stream_session, ev.rid)]
        if isinstance(ev, FinishEvent):
            rec.finish = ev.result.finish.value
            rec.finished_at = now
            rec.queue_wait_ms = ev.result.queue_wait_ms
        else:
            rec.tokens.append(int(ev.token))
            rec.times.append(now)
        return ev, now

    def run(self, on_tick=None) -> None:
        """Lead-in, then the window; ``on_tick(now)`` is called between
        events (the traced run starts the profiler there)."""
        import jax

        from repro.serve import FinishEvent

        lead = float(self.mix["lead_in_s"])
        start = time.perf_counter()
        self.t0 = start + lead
        t_end = self.t0 + self.seconds
        if self.mix["loop"] == "closed":
            for _ in range(self.mix["clients"]):
                self._submit(next(self.source), start, start)
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if on_tick is not None:
                on_tick(now)
            if self.mix["loop"] == "open":
                while i < len(self.plan) and self.t0 + self.plan[i].due <= now:
                    self._submit(self.plan[i], self.t0 + self.plan[i].due, now)
                    i += 1
                if self.stream is None:
                    nxt = (self.t0 + self.plan[i].due if i < len(self.plan)
                           else t_end)
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, min(nxt, t_end) - now))
                    continue
            ev, now = self._next()
            if (self.mix["loop"] == "closed" and isinstance(ev, FinishEvent)
                    and now < t_end):
                self._submit(next(self.source), now, now)
        self.t1 = t_end

    def drain(self) -> None:
        """Serve what is in flight to the end, submitting nothing more."""
        while self.stream is not None:
            self._next()


# ------------------------------------------------------------------ metrics


def pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def end_to_end(client: Client) -> dict:
    t0, t1 = client.t0, client.t1
    toks = sum(1 for r in client.recs for t in r.times if t0 <= t < t1)
    due = [r for r in client.recs if t0 <= r.due < t1]
    ttft = [((r.times[0] if r.times and r.times[0] < t1 else t1) - r.due) * 1e3
            for r in due]
    itl = [(b - a) * 1e3 for r in client.recs
           for a, b in zip(r.times, r.times[1:]) if t0 <= a and b < t1]
    return {
        "tokens_per_s": toks / (t1 - t0),
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p95_ms": pct(ttft, 95),
        "itl_p95_ms": pct(itl, 95),
        "samples": {"tokens": toks, "ttft": len(ttft), "itl": len(itl)},
    }


# ------------------------------------------------------------------ checks


def finished(client: Client) -> List[Rec]:
    return [r for r in client.recs
            if r.finish in ("max_new", "eos") and r.finished_at < client.t1]


def sample_finished(client: Client, seed: int, k: int) -> List[Rec]:
    """``k`` requests finished by the close of the window, drawn from the
    seed, the one with most prompt and served tokens always among them."""
    done = finished(client)
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].prompt.size + len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = [longest] + list(rng.choice(rest, min(k - 1, len(rest)),
                                       replace=False))
    return [done[i] for i in pick]


def divider_request(client: Client, seed: int) -> Optional[Rec]:
    """A request finished in the window, drawn from the seed among those
    of the largest prompt bucket, so that a cell's check runs one shape."""
    done = finished(client)
    if not done:
        return None
    top = max(TR.bucket(r.prompt.size) for r in done)
    done = [r for r in done if TR.bucket(r.prompt.size) == top]
    return done[int(np.random.default_rng(seed).integers(len(done)))]


def check(params, conf: dict, cfg, sample: List[Rec], div_req: Optional[Rec],
          control: str = "") -> dict:
    """The numbers ``correct`` compares, each with its limit.  With
    ``control`` the low-precision reference takes the program's place:
    ``logit_gap`` is then the gap of the tokens the control puts first."""
    import blocks
    import divsites
    import reference

    length = conf["check_length"]
    gaps, lows = [], []
    for r in sample:
        g, lo = reference.widest_gaps(conf, params, r.prompt,
                                      np.asarray(r.tokens), length, control)
        gaps.append(g)
        lows.append(lo)
    div = {}
    if div_req is not None:
        n = div_req.prompt.size
        P = TR.bucket(n)
        toks = np.zeros(P, np.int32)
        toks[P - n:] = div_req.prompt
        sites = blocks.get(conf["block"]).DIVISION_SITES
        seen = divsites.capture(cfg, params, toks, P - n, conf["max_seq"],
                                sites)
        div = divsites.compare(sites, seen, conf["posit_bits"])
    served = lows if control else gaps
    limits = conf["limits"]
    out = {
        "logit_gap": {"value": max(served) if served else None,
                      "limit": limits["logit_gap"]},
        "divider_mismatches": {"value": div.get("quotient_mismatches"),
                               "limit": limits["divider_mismatches"]},
        "flash_off_grid": {"value": div.get("grid_off"),
                           "limit": limits["flash_off_grid"]},
    }
    out["_detail"] = {"requests": len(sample), "control": control or None,
                      "served_tokens": int(sum(len(r.tokens) for r in sample)),
                      "program_logit_gap": max(gaps) if gaps else None,
                      "gaps": served,
                      "divider_prompt": None if div_req is None else n, **div}
    return out


def is_correct(checks: dict) -> bool:
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"]
               for name, c in checks.items() if not name.startswith("_"))


# ------------------------------------------------------------------ trace


class Tracer:
    """Traces the last ``TRACE_SECONDS`` of the window, so that stopping the
    profiler, which takes seconds, falls after the window; the traced span
    is ``bench.window``."""

    def __init__(self, client: Client, out_dir: Path):
        self.client, self.dir = client, out_dir
        self.state = "idle"
        self.marks = {}

    def __call__(self, now: float) -> None:
        import jax

        c = self.client
        if c.t0 is None or self.state != "idle":
            return
        if now >= c.t0 + c.seconds - min(TRACE_SECONDS, c.seconds):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.marks["start"] = (time.perf_counter(), c.counters())
            self.state = "on"

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        self.marks["stop"] = (time.perf_counter(), self.client.counters())
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def trace_record(tracer: Tracer, client: Client) -> dict:
    import trace_reduce as TRC

    events = TRC.load(TRC.find_xplane(str(tracer.dir)))
    red = TRC.Reduced(events)
    (a, ca), (b, cb) = tracer.marks["start"], tracer.marks["stop"]
    steps = cb["decode_steps"] - ca["decode_steps"]
    decode_rows, prompts = [], []
    for r in client.recs:
        for k, t in enumerate(r.times):
            if a <= t < b:
                if k == 0:
                    prompts.append(int(r.prompt.size))
                else:
                    decode_rows.append(int(r.prompt.size) + k)
    return {"reduced": red, "decode_steps": steps,
            "decode_live_rows": decode_rows, "prefill_prompts": prompts}


# ------------------------------------------------------------------ run


def prepare(cell: dict, seed: int) -> dict:
    """Set-up: the device check, the compile cache, the weights from the
    seed, the engine, and one warm-up request per prompt bucket."""
    import jax

    import blocks

    conf, mix = cell["config"], cell["mix"]
    block = blocks.get(conf.get("block"))
    devs = devices(cell["chips"])
    dev = devs[0]
    cache_dir = enable_compile_cache()
    peaks = peaks_for(dev.device_kind)
    log(f"[setup] {dev.platform} {dev.device_kind} x{len(devs)}; compile cache "
        f"{cache_dir}")
    log(f"[setup] jax up: {time.perf_counter() - T_START:.3f} s since start")
    params = block.make_weights(conf["model"], seed)
    jax.block_until_ready(params)
    log(f"[setup] weights: {time.perf_counter() - T_START:.3f} s since start")
    eng = build_engine(conf, params)
    buckets = TR.buckets(mix, conf["max_seq"])
    warm_up(eng, buckets, conf["model"]["vocab"])
    log(f"[setup] warm-up of buckets {buckets}: "
        f"{time.perf_counter() - T_START:.3f} s since start")
    return {"conf": conf, "mix": mix, "devs": devs, "dev": dev,
            "peaks": peaks, "params": params, "eng": eng}


def run(cell: dict, seed: int, seconds: float, traced: bool,
        control: str = "") -> dict:
    counter = CompileCounter()
    ctx = prepare(cell, seed)
    conf, mix, eng, params = ctx["conf"], ctx["mix"], ctx["eng"], ctx["params"]
    devs, dev, peaks = ctx["devs"], ctx["dev"], ctx["peaks"]
    del ctx
    client = Client(eng, mix, seed, seconds, conf["model"]["vocab"])
    tracer = None
    if traced:
        tdir = ROOT / ".bench_trace" / cell["name"]
        shutil.rmtree(tdir, ignore_errors=True)
        tracer = Tracer(client, tdir)

    def tick(now):
        if not counter.armed and client.t0 is not None and now >= client.t0:
            counter.armed = True
            client.counts0 = client.counters()
        if tracer is not None:
            tracer(now)

    client.run(tick)
    counter.armed = False
    counts1 = client.counters()
    if tracer is not None:
        tracer.stop()
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    e2e = end_to_end(client)
    log(f"[window] {seconds} s: {len(client.recs)} requests submitted, "
        f"samples {e2e['samples']}; compilations in the window: {counter.count}")

    # free the program's serving state before the reference runs
    cfg = eng.cfg
    del eng
    client.eng = client.stream = None
    gc.collect()

    due = [r for r in client.recs if client.t0 <= r.due < client.t1]
    failed = sum(1 for r in due if r.finish not in (None, "max_new", "eos"))
    sample = sample_finished(client, seed, conf["check_requests"])
    checks = check(params, conf, cfg, sample, divider_request(client, seed),
                   control)
    rec = {
        "block": conf["block"], "model": conf["model"],
        "slots": conf["slots"], "peaks": peaks,
        "loop": mix["loop"], "requests": client.recs,
        "window": (client.t0, client.t1),
        "counters": {k: counts1[k] - client.counts0[k] for k in counts1},
        "memory_peak_bytes": mem_peak,
    }
    rec["counters"]["slot_steps"] = rec["counters"]["decode_steps"] * conf["slots"]
    out = {
        "correct": is_correct(checks),
        "attempted": len(due),
        "failed": failed,
        "setup_s": client.t0 - T_START,
        "e2e": e2e,
        "compiles_in_window": counter.count,
        "checks": checks,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": mem_peak},
        "rec": rec,
    }
    if tracer is not None and tracer.state == "done":
        tr = trace_record(tracer, client)
        rec["trace"] = tr
        red = tr["reduced"]
        out["device"]["busy_s"] = red.busy_s
        out["device"]["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return out


def result_line(cell: dict, out: dict, traced: bool) -> dict:
    if traced:
        metrics = {}
        for m in cell["per_layer"]:
            v = read_metric(m["name"], out["rec"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = dict(out["e2e"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if vals.get(m["name"]) is not None}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if traced and "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = {k: v for k, v in out["checks"].items()
                      if not k.startswith("_")}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"[error] {e}")
        return 2
    line = result_line(cell, out, bool(args.trace))
    log(f"[result] compiles_in_window {out['compiles_in_window']}; "
        f"samples {out['e2e']['samples']}; checked {out['checks']['_detail']}")
    print(f"compiles_in_window {out['compiles_in_window']}", flush=True)
    for name, c in line["checks"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
