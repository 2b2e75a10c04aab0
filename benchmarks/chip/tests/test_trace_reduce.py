"""The trace reduction on hand-made events and on a recorded v5e trace."""

from pathlib import Path

import pytest

import trace_reduce as TRC

DEV = "/device:TPU:0"
FIXTURE = Path(__file__).resolve().parent / "v5e_chat_trace.json.gz"


def _op(name, start, dur):
    return [DEV, "XLA Ops", name, float(start), float(dur)]


def _mod(name, start, dur):
    return [DEV, "XLA Modules", name, float(start), float(dur)]


def _host(name, start, dur):
    return ["/host:CPU", "host", name, float(start), float(dur)]


ATTN = "%posit_flash_attention.3 = f32[480,8,128]{2,1,0} custom-call(f32[480,8,128] %x)"
DIV = "%posit_fused_div_rowwise_pallas.2 = f32[64,256]{1,0} custom-call(f32[64,256] %a)"
LOOP = "%while.2 = (s32[], bf16[32,1,960]) while((s32[], bf16[32,1,960]) %t), body=%b"
FUS = "%pad_convert_fusion.5 = f32[160,2048,128]{2,1,0} fusion(bf16[160,2048,64] %c), kind=kLoop"


def _events():
    return [
        _host("bench.window", 0, 1000),
        _host("bench.next", 0, 600),
        _host("bench.wait", 600, 400),
        # decode executions at 100 and 300, a prefill at 500
        _mod("jit__lambda(111)", 100, 50), _op(LOOP, 100, 50),
        _op(ATTN, 105, 20), _op(FUS, 130, 10),
        _mod("jit__lambda(111)", 300, 50), _op(LOOP, 300, 50),
        _op(ATTN, 305, 20), _op(FUS, 330, 10),
        _mod("jit__lambda(222)", 500, 80), _op(ATTN, 510, 30), _op(DIV, 550, 20),
        # an op that starts before the window is clipped to it
        _op(FUS, -30, 40),
    ]


def test_op_name():
    assert TRC.op_name(ATTN) == ("posit_flash_attention", "custom-call")
    assert TRC.op_name(LOOP) == ("while", "while")
    assert TRC.op_name(FUS) == ("pad_convert_fusion", "fusion")


def test_busy_window_and_idle_gaps():
    red = TRC.Reduced(_events())
    assert red.window_s == pytest.approx(1e-6)
    # busy: [0, 10) from the clipped op, then the three modules
    assert red.busy_s == pytest.approx((10 + 50 + 50 + 80) * 1e-9)
    gaps = dict(red.idle_gaps())
    # gaps [10,100), [150,300), [350,500) fall in bench.next; [580,1000)
    # has its midpoint in bench.wait
    assert gaps["bench.next"] == pytest.approx((90 + 150 + 150) * 1e-9)
    assert gaps["bench.wait"] == pytest.approx(420 * 1e-9)


def test_top_ops_leave_containers_out():
    red = TRC.Reduced(_events())
    top = dict(red.top_ops())
    assert "while" not in top
    assert top["posit_flash_attention"] == pytest.approx(70e-9)
    assert top["pad_convert_fusion"] == pytest.approx(30e-9)


def test_decode_and_prefill_told_apart_by_count():
    red = TRC.Reduced(_events())
    st = TRC.model_steps(red, decode_steps=2)
    assert [m[2] for m, _ in st["decode"]] == ["jit__lambda(111)"] * 2
    assert [m[2] for m, _ in st["prefill"]] == ["jit__lambda(222)"]
    assert TRC.seconds_of(red, st["decode"]) == pytest.approx(100e-9)
    assert TRC.seconds_of(red, st["decode"], TRC.is_attention) == pytest.approx(40e-9)
    div = lambda o: "div_rowwise" in TRC.op_name(o[2])[0]  # noqa: E731
    assert TRC.seconds_of(red, st["prefill"], div) == pytest.approx(20e-9)


def test_recorded_v5e_trace():
    """The first 300 ms of the traced span of a `smollm-360m.chat` run on
    one v5e (TPU v5 lite): the events ``load`` keeps (device ops and
    modules, the harness's host spans), stored as gzipped JSON."""
    events = TRC.load(str(FIXTURE))
    red = TRC.Reduced(events)
    assert 0 < red.busy_s <= red.window_s
    names = {TRC.op_name(e[2])[0] for e in red.ops}
    assert "posit_flash_attention" in names
    assert "posit_fused_div_rowwise_pallas" in names
    progs = {m[2] for m, ops in red.module_ops() if any(TRC.is_attention(o) for o in ops)}
    assert progs, "no model step found"
    top = red.top_ops(10)
    assert top[0][1] >= top[-1][1] > 0
    assert sum(v for _, v in red.idle_gaps()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
