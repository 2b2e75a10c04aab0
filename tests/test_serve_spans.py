"""The serving engine's profiler spans, its cumulative counters, and the
names of its executables.

Spans are ``jax.profiler.TraceAnnotation``s, so they land in the same
trace as the device ops; their keyword arguments come back as event
stats.  Each test records a CPU trace of a small stream and reads it with
``jax.profiler.ProfileData``.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve.engine import COUNTERS, TokenEvent

B, MAX_SEQ = 2, 64
# three requests on two slots, so the third waits for a slot; prompt
# lengths 5, 9 and 3 land in buckets 8, 16 and 8
STREAM = [(np.arange(1, 6, dtype=np.int32), 4),
          (np.arange(2, 11, dtype=np.int32), 2),
          (np.arange(3, 6, dtype=np.int32), 3)]
CHILDREN = {"serve.admit": ("serve.prefill", "serve.scatter",
                            "serve.first_token"),
            "serve.step": ("serve.dispatch", "serve.sync", "serve.record")}


@pytest.fixture(scope="module", params=["dense", "paged"])
def engine(request):
    cfg = get_config("smollm-360m", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_batch=B, max_seq=MAX_SEQ, kv_layout=request.param))
    eng.serve([Request(p, max_new=m) for p, m in STREAM])   # compile
    return eng


def _requests():
    return [Request(p, max_new=m) for p, m in STREAM]


def _traced(tmp_path, fn):
    """``fn()``'s result and the ``serve.*``/``test.*`` host spans it left
    in a profiler trace: ``[name, start_ns, end_ns, stats]`` by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "test.")):
                    spans.append([e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dict(e.stats)])
    return out, sorted(spans, key=lambda s: s[1])


def _stream(eng, reqs):
    """Submit ``reqs`` and drain the stream, each ``next`` inside a
    test-side span; returns every request's tokens."""
    toks = {eng.submit(r): [] for r in reqs}
    stream = eng.serve_stream()
    while True:
        with TraceAnnotation("test.next"):
            ev = next(stream, None)
        if ev is None:
            return list(toks.values())
        if isinstance(ev, TokenEvent):
            toks[ev.rid].append(ev.token)


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_spans_args_nesting_and_counters(engine, tmp_path):
    c0 = engine.counters()
    toks, spans = _traced(tmp_path, lambda: _stream(engine, _requests()))
    c1 = engine.counters()
    by = lambda n: [s for s in spans if s[0] == n]  # noqa: E731

    # one serve.admit per admitted request, with its args
    admits = by("serve.admit")
    assert sorted(s[3]["rid"] for s in admits) == [0, 1, 2]
    for s in admits:
        prompt, _ = STREAM[s[3]["rid"]]
        assert s[3]["prompt"] == len(prompt)
        want = len(prompt) if engine.sc.kv_layout == "paged" else \
            max(8, 1 << (len(prompt) - 1).bit_length())
        assert s[3]["bucket"] == want
        assert s[3]["wait_ms"] >= 0.0
    # the third request waited for the first free slot
    wait = {s[3]["rid"]: s[3]["wait_ms"] for s in admits}
    assert wait[2] >= wait[0]

    # one serve.step per decode step, every child inside its parent
    steps = by("serve.step")
    assert len(steps) == c1["decode_steps"] - c0["decode_steps"] > 0
    assert [s[3]["step"] for s in steps] == list(range(1, len(steps) + 1))
    for parent, kids in CHILDREN.items():
        for kid in kids:
            found = by(kid)
            assert len(found) == len(by(parent)), kid
            assert all(any(_inside(k, p) for p in by(parent))
                       for k in found), kid

    # the yield rule: every serve.* span lies inside one next(stream)
    nexts = by("test.next")
    for s in spans:
        if s[0].startswith("serve."):
            assert any(_inside(s, n) for n in nexts), s

    # the span args are the counters' increments
    live = sum(s[3]["live_rows"] for s in steps)
    read = sum(s[3]["read_rows"] for s in steps)
    scan = sum(s[3]["scan_rows"] for s in steps)
    assert live == c1["kv_rows_live"] - c0["kv_rows_live"]
    assert read == c1["kv_rows_read"] - c0["kv_rows_read"]
    assert scan == c1["kv_rows_scanned"] - c0["kv_rows_scanned"]
    assert read == len(steps) * B * MAX_SEQ
    # XLA decode attention scans every row it reads
    assert scan == read
    assert sum(s[3]["active"] for s in steps) == \
        c1["active_slot_steps"] - c0["active_slot_steps"]
    # a request of prompt n decodes its tokens 2..m at n + 1 .. n + m - 1
    # live rows (its first token comes from the prefill)
    assert live == sum(len(p) + k for p, m in STREAM for k in range(1, m))
    assert sum(s[3]["bucket"] for s in admits) == \
        c1["prefill_rows"] - c0["prefill_rows"]
    assert sum(s[3]["prompt"] for s in admits) == \
        c1["prompt_tokens"] - c0["prompt_tokens"]
    assert c1["admissions"] - c0["admissions"] == len(admits)
    assert [len(t) for t in toks] == [m for _, m in STREAM]


def test_tokens_identical_with_the_profiler_on(engine, tmp_path):
    off = [o.tolist() for o in engine.serve(_requests())]
    on, spans = _traced(tmp_path, lambda: engine.serve(_requests()))
    assert spans, "no serve.* span recorded"
    assert [o.tolist() for o in on] == off


def test_counters_sum_the_sessions(engine):
    c0 = engine.counters()
    stats = []
    for _ in range(2):
        engine.serve(_requests())
        stats.append(engine.last_serve_stats)
        c = engine.counters()
        assert all(c[k] >= c0[k] for k in COUNTERS)
    c2 = engine.counters()
    for k in COUNTERS:
        assert c2[k] - c0[k] == stats[0][k] + stats[1][k], k
    assert stats[0]["decode_steps"] > 0 and stats[0]["kv_rows_live"] > 0
    # warm-up sessions are not counted
    engine.warmup(prompt_lens=[8])
    assert engine.counters() == c2


FUSED_SEQ, TILE = 384, 128   # the decode kernel's kv tile at this max_seq
# a 150-token prompt lands in bucket 256 (start 106) and decodes over
# three tiles; the others take one, and each slot parks in between
FUSED_STREAM = [(np.arange(1, 151, dtype=np.int32) % 97 + 1, 3),
                (np.arange(2, 7, dtype=np.int32), 2),
                (np.arange(3, 6, dtype=np.int32), 3)]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_parked_slots_and_rows_scanned(layout, tmp_path, monkeypatch):
    """Under the fused flash decode a freed slot parks at ``pos == start
    == 0`` and stays there, and ``kv_rows_scanned`` counts the rows of
    the tiles the kernel scans: each slot's tiles from ``start`` to
    ``pos``, recounted here from the mirrors each step ships."""
    cfg = get_config("smollm-360m", smoke=True, fused=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_batch=B, max_seq=FUSED_SEQ, kv_layout=layout))
    reqs = [Request(p, max_new=m) for p, m in FUSED_STREAM]

    shipped, parked = [], []
    step = eng._step

    def watched(st):
        shipped.append((st.pos.copy(), st.start.copy()))
        step(st)
        idle = ~st.sched.active
        parked.append(int(idle.sum()))
        assert (st.pos[idle] == 0).all() and (st.start[idle] == 0).all()

    monkeypatch.setattr(eng, "_step", watched)
    c0 = eng.counters()
    outs, spans = _traced(tmp_path, lambda: eng.serve(reqs))
    c1 = eng.counters()
    assert sum(parked) > 0, "no slot was ever parked"
    assert [len(o) for o in outs] == [m for _, m in FUSED_STREAM]

    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(shipped)
    recount = sum(int(((pos // TILE) - (start // TILE) + 1).sum()) * TILE
                  for pos, start in shipped)
    scan = sum(s[3]["scan_rows"] for s in steps)
    assert scan == recount == c1["kv_rows_scanned"] - c0["kv_rows_scanned"]
    assert eng.last_serve_stats["kv_rows_scanned"] == scan
    assert scan < c1["kv_rows_read"] - c0["kv_rows_read"]
    # the long request scans three tiles a step
    assert max(s[3]["scan_rows"] for s in steps) >= 3 * TILE


def test_ttft_stats_follow_the_results(engine):
    engine.serve(_requests())
    assert sorted(engine.last_serve_stats["ttft_ms"]) == \
        sorted(r.ttft_ms for r in engine.last_results)


def test_packed_admission_span(tmp_path):
    cfg = get_config("smollm-360m", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_batch=B, max_seq=MAX_SEQ, packed_prefill=True))
    reqs = [Request(np.arange(1, 6, dtype=np.int32), max_new=2),
            Request(np.arange(2, 5, dtype=np.int32), max_new=2)]
    c0 = eng.counters()
    _, spans = _traced(tmp_path, lambda: eng.serve(reqs))
    packs = [s for s in spans if s[0] == "serve.admit_pack"]
    # both prompts land in bucket 8 and admit as one pack of two
    assert [s[3]["segments"] for s in packs] == [2]
    assert packs[0][3]["rid"] == 0
    assert packs[0][3]["prompt"] == 5 + 3
    assert packs[0][3]["bucket"] == 2 * 8 == \
        eng.counters()["prefill_rows"] - c0["prefill_rows"]
    assert packs[0][3]["wait_ms"] >= 0.0


def test_executables_are_named(engine):
    sds = jax.ShapeDtypeStruct
    p = jax.tree.map(lambda a: sds(a.shape, a.dtype), engine.params)
    cache = jax.eval_shape(lambda: T.init_cache(engine.cfg, B, MAX_SEQ))
    mini = jax.eval_shape(lambda: T.init_cache(engine.cfg, 1, MAX_SEQ))
    vec = sds((B,), jnp.int32)
    dec = engine._decode.lower(p, cache, sds((B, 1), jnp.int32), vec, vec)
    pre = engine._prefill.lower(p, mini, sds((1, 8), jnp.int32),
                                sds((1,), jnp.int32))
    assert "module @jit_serve_decode " in dec.as_text()
    assert "module @jit_serve_prefill " in pre.as_text()
    for key in engine.executable_counts():
        fn = getattr(engine, f"_{key}")
        assert fn.__wrapped__.__name__ == f"serve_{key}", key
