"""The engine's ``serve.*`` spans in a trace: what ``serve_spans.load``
keeps of them, the numbers ``serve_spans`` reads from them, and the trace
readers that came before them."""

import glob
import json
from pathlib import Path

import pytest

import run as R
import serve_spans as SP
import trace_reduce as TRC

HERE = Path(__file__).resolve().parent
OLD = HERE / "v5e_chat_trace.json.gz"
SMOLLM = json.loads((HERE.parent / "configs" / "smollm-360m.json").read_text())


def _host(name, start, dur, **args):
    e = ["/host:CPU", "host", name, float(start), float(dur)]
    return e + [args] if name.startswith("serve.") else e


def _rec(events, **trace):
    return {"block": SMOLLM["block"], "model": SMOLLM["model"],
            "peaks": R.peaks_for("TPU v5 lite"),
            "trace": dict(reduced=TRC.Reduced(events), **trace)}


def test_serve_spans_load_keeps_serve_spans_with_their_args(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.next"):
        with jax.profiler.TraceAnnotation("serve.step", step=3, live_rows=7):
            pass
        with jax.profiler.TraceAnnotation("other.span", rid=1):
            pass
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    assert [e[2] for e in TRC.load(path) if e[1] == "host"] == ["bench.next"]
    (step,) = SP.load(path)
    assert step[1:3] == ["host", "serve.step"]
    assert step[5] == {"step": 3, "live_rows": 7}


def test_span_readers_on_hand_made_spans():
    events = [
        _host("bench.window", 0, 1000),
        _host("serve.admit", 0, 100, rid=0, prompt=5, bucket=8, wait_ms=2.0),
        _host("serve.admit", 400, 300, rid=1, prompt=9, bucket=16,
              wait_ms=6.0),
        _host("serve.admit", 900, 200, rid=2, prompt=2, bucket=8,
              wait_ms=40.0),           # cut at the window's close
        _host("serve.step", 100, 300, step=1, active=1, live_rows=6,
              read_rows=128),
        _host("serve.step", 700, 200, step=2, active=2, live_rows=20,
              read_rows=128),
    ]
    rec = _rec(events)
    assert SP.admit_wait_p50_ms(rec) == pytest.approx(6.0)
    assert SP.prefill_p50_ms(rec) == pytest.approx(200e-6)
    # admit 100 + 300 + 100 (clipped) ns against steps 300 + 200 ns
    assert SP.admit_stall_share(rec) == pytest.approx(50.0)
    assert SP.kv_live_share(rec) == pytest.approx(
        100 * 26 / 256)
    assert SP.prefill_pad_share(rec) == pytest.approx(
        100 * (1 - 16 / 32))


def test_span_readers_read_nothing_without_serve_spans():
    rec = _rec(TRC.load(str(OLD)), decode_steps=2, decode_live_rows=[],
               prefill_prompts=[])
    for name, read in SP.READERS.items():
        assert read(rec) is None, name
        assert read({}) is None, name
    assert SP.serve_idle_share(rec["trace"]["reduced"]) == 0.0


def test_trace_readers_read_the_old_fixture_as_before():
    """What the readers gave on the recorded v5e trace before the engine
    had spans, for one made-up set of live rows and prompts."""
    rows = [200 + 7 * i for i in range(21)] * 2
    rec = _rec(TRC.load(str(OLD)), decode_steps=2, decode_live_rows=rows,
               prefill_prompts=[180])
    want = {"device_idle_share": 94.79432749872745,
            "flash_decode_roofline": 0.3648818385168031,
            "mfu.decode": 0.05154363555589567,
            "mfu.prefill": None, "srt_div_prefill_roofline": None}
    for name, value in want.items():
        got = R.read_metric(name, rec)
        assert got == (value if value is None else pytest.approx(value)), name


NEW = HERE / "v5e_chat_spans.json.gz"


def test_recorded_v5e_spans_fixture():
    """600 ms of the traced span of a `smollm-360m.chat` run on one v5e
    (TPU v5 lite, seed 2147483301), from 50 ms before an admission:
    ``record_trace.py``'s trimmed copy of what ``trace_reduce.load`` and
    ``serve_spans.load`` keep, the engine's spans with their args among
    it."""
    red = TRC.Reduced(TRC.load(str(NEW)))
    names = {m[2].split("(")[0] for m in red.modules}
    assert {"jit_serve_decode", "jit_serve_prefill"} <= names
    assert all(len(e) == 6 for e in red.host if e[2].startswith("serve."))
    # what each reader read when the fixture was recorded
    want = {"admit_wait_p50_ms": 0.12675500000477768,
            "prefill_p50_ms": 18.3571295,
            "admit_stall_share": 6.13160991139943,
            "kv_live_share": 11.4251708984375,
            "prefill_pad_share": 42.1875}
    rec = {"trace": {"reduced": red}}
    for name, value in want.items():
        assert SP.READERS[name](rec) == pytest.approx(value), name


def test_engine_spans_label_the_idle_gaps():
    red = TRC.Reduced(TRC.load(str(NEW)))
    gaps = dict(red.idle_gaps(100))
    idle = sum(gaps.values())
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    serve = sum(v for k, v in gaps.items() if k.startswith("serve."))
    assert serve >= 0.8 * idle
    assert gaps.get("bench.next", 0.0) < 0.2 * idle
    assert SP.serve_idle_share(red) == pytest.approx(100 * serve / idle)


def test_split_by_name_equals_split_by_count():
    red = TRC.Reduced(TRC.load(str(NEW)))
    steps = sum(1 for e in red.host if e[2] == "serve.step")
    by_count = TRC.model_steps(red, steps)
    model = [(m, ops) for m, ops in red.module_ops()
             if any(TRC.is_attention(o) for o in ops)]
    for part in ("decode", "prefill"):
        by_name = [m for m, _ in model
                   if m[2].startswith(f"jit_serve_{part}(")]
        assert by_name, part
        assert [m for m, _ in by_count[part]] == by_name, part
