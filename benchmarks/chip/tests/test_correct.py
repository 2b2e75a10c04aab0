"""``correct`` against broken timed paths and the low-precision control.

Each test drives the rest of a run (set-up, lead-in, window, check) at
smoke size on the CPU, with the look for a chip stood in for here and the
program broken underneath where the fault says so.  Slow: each run
compiles the interpret-mode kernels (about a minute)."""

import json
from pathlib import Path

import pytest

import run as R

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def cell():
    smoke = json.loads((HERE / "smoke.json").read_text())
    conf = json.loads((HERE.parent / "configs" / "smollm-360m.json").read_text())
    conf.update(smoke["config_overrides"])
    conf["model"] = dict(conf["model"], **smoke["model_overrides"])
    conf["limits"] = smoke["limits"]
    bench = json.loads((HERE.parents[2] / "BENCHMARK.json").read_text())
    return {"name": "smoke", "chips": 1, "config": conf, "mix": smoke["mix"],
            "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"]}


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    """The CPU stands in for the chip: its devices, the v5e's peaks."""
    import jax

    peaks = json.loads((HERE.parent / "peaks.json").read_text())
    monkeypatch.setattr(R, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(R, "peaks_for",
                        lambda kind: peaks["devices"]["TPU v5 lite"])


@pytest.fixture
def fresh_traces():
    """Drop JAX's traces before and after a test that breaks code below a
    jitted function, so neither side reuses the other's."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(cell, control=""):
    return R.run(cell, 11, 3.0, traced=False, control=control)


def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    d = out["checks"]["_detail"]
    assert d["quotients"] > 0 and d["grid_outputs"] > 0, d
    # every layer's two RMSNorms and the final one; every layer's attention
    layers = cell["config"]["model"]["n_layers"]
    assert (d["quotient_sites"], d["grid_sites"]) == (2 * layers + 1, layers), d
    by_site = {k: v["calls"] for k, v in d["by_site"].items()}
    assert by_site == {"posit_rmsnorm_div": 2 * layers + 1,
                       "posit_flash_attention": layers}, d


@pytest.fixture
def recording_block(monkeypatch):
    """``tests/blocks/recording.py`` found as a block beside the others."""
    import sys

    import blocks

    monkeypatch.setattr(blocks, "__path__",
                        [*blocks.__path__, str(HERE / "blocks")])
    yield
    mod = sys.modules.pop("blocks.recording", None)
    if mod is not None:
        delattr(blocks, "recording")


def test_block_added_as_a_file_is_reached(cell, recording_block):
    """A configuration that names another block reaches it, by its file
    alone, through the weights, the reference, the division sites and the
    per-layer metrics' counts."""
    import blocks
    import trace_reduce as TRC

    conf = dict(cell["config"], block="recording")
    out = R.run(dict(cell, config=conf), 11, 3.0, traced=False)
    assert out["correct"], out["checks"]
    assert out["rec"]["block"] == "recording"
    calls = blocks.get("recording").CALLS
    assert {"make_weights", "logits", "DIVISION_SITES"} <= calls, calls
    # the readers of a recorded v5e trace, for a run of that block
    red = TRC.Reduced(TRC.load(str(HERE / "v5e_chat_spans.json.gz")))
    steps = sum(1 for e in red.host if e[2] == "serve.step")
    rec = dict(out["rec"], trace={"reduced": red, "decode_steps": steps,
                                  "decode_live_rows": [300, 500],
                                  "prefill_prompts": [180]})
    for name in ("mfu.decode", "mfu.prefill", "flash_decode_roofline",
                 "srt_div_prefill_roofline"):
        assert R.read_metric(name, rec) is not None, name
    assert {"decode_flops", "prefill_flops", "flash_decode_bytes",
            "rmsnorm_divide_bytes"} <= calls, calls


def test_decode_step_that_returns_its_cache_unchanged(cell, monkeypatch):
    from repro.models import transformer as T

    real = T.decode_step

    def stale(params, cfg, cache, *a, **k):
        out = real(params, cfg, cache, *a, **k)
        return (out[0], cache) + tuple(out[2:])

    monkeypatch.setattr(T, "decode_step", stale)
    out = _run(cell)
    assert not out["correct"], out["checks"]


def test_token_altered_where_it_is_sampled(cell, monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._greedy_h_impl

    def bump(self, lg, health):
        out = real(self, lg, health)
        return out.at[:, 0].set((out[:, 0] + 1) % self.cfg.vocab)

    monkeypatch.setattr(ServeEngine, "_greedy_h_impl", bump)
    out = _run(cell)
    assert not out["correct"], out["checks"]


def test_divider_replaced_by_float_division(cell, monkeypatch):
    # the binding the model's RMSNorm calls, in the engine and the check
    from repro.models import layers

    monkeypatch.setattr(layers, "posit_rmsnorm_div",
                        lambda x, rms, cfg: x / rms)
    out = _run(cell)
    assert out["checks"]["divider_mismatches"]["value"] > 0, out["checks"]
    assert not out["correct"]


def test_flash_normalizer_replaced_by_float_division(cell, monkeypatch,
                                                     fresh_traces):
    # the in-kernel acc / l of the fused flash attention kernel
    from repro.kernels import posit_flash_attn

    monkeypatch.setattr(posit_flash_attn, "divide_floats_block",
                        lambda fmt, a, b, variant=None: a / b)
    out = _run(cell)
    assert out["checks"]["flash_off_grid"]["value"] > 0, out["checks"]
    assert not out["correct"]


def test_fp8_control_fails_the_limit(cell):
    out = _run(cell, control="fp8")
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"], c
    assert out["correct"] is False, out["checks"]
