"""Per-layer readers on a hand-made window, and the per-layer entries of
``BENCHMARK.json`` against the cells that report them."""

import json
import types
from pathlib import Path

import numpy as np
import pytest

import run as R

HERE = Path(__file__).resolve().parent
BENCH = json.loads((HERE.parents[2] / "BENCHMARK.json").read_text())


def _window(loop="open"):
    """Forty requests due 1 s apart in a 40 s window (t0 = 100), each with
    its first token 10 + 5 * i ms after it was due, the last one with none
    by the close; one request of the lead-in."""
    t0, t1 = 100.0, 140.0
    recs = []
    for i in range(40):
        due = t0 + i
        r = R.Rec(np.zeros(4, np.int32), due, submit=due + 0.002)
        if i < 39:
            r.times = [due + (10 + 5 * i) / 1e3, due + 1.0]
            r.finish, r.finished_at = "max_new", due + 0.5
            r.queue_wait_ms = float(i)
        recs.append(r)
    lead = R.Rec(np.zeros(4, np.int32), t0 - 1, submit=t0 - 1)
    lead.times, lead.finish, lead.finished_at = [t0 - 0.5], "max_new", t0 + 0.1
    lead.queue_wait_ms = 1000.0
    recs.append(lead)
    return {"loop": loop, "requests": recs, "window": (t0, t1)}


def test_ttft_p95_open_loop_is_the_end_to_end_rule():
    rec = _window()
    client = types.SimpleNamespace(t0=rec["window"][0], t1=rec["window"][1],
                                   recs=rec["requests"])
    got = R.read_metric("ttft_p95_ms.open_loop", rec)
    assert got == pytest.approx(R.end_to_end(client)["ttft_p95_ms"])
    # the request with no token by the close counts its 1 s wait
    ttft = [10 + 5 * i for i in range(39)] + [1000.0]
    assert got == pytest.approx(np.percentile(ttft, 95))
    assert R.read_metric("ttft_p95_ms.open_loop", _window("closed")) is None


def test_queue_wait_p50_counts_requests_finished_in_the_window():
    # the lead-in's request finished in the window too; the unfinished one
    # does not count
    waits = [float(i) for i in range(39)] + [1000.0]
    assert R.read_metric("queue_wait_p50_ms", _window()) == \
        pytest.approx(np.percentile(waits, 50))


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert (HERE.parent / "metrics" / f"{metric['name']}.py").is_file()
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in BENCH["end_to_end"]}
    for cell in metric.get("workloads", cells):
        assert cell in cells
        assert cell in e2e[metric["moves"]], (cell, metric["moves"])
