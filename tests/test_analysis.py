"""Static analysis: the prover proves the real datapath, REFUTES known-bad
fixtures with actionable messages, and the jaxpr/AST linter both passes the
real tree and fires on planted violations."""

import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    DEFAULT_RULES,
    build_traced_entries,
    check_otf_width,
    check_residual_frame,
    check_selection_containment,
    lint_kernel_sources,
    prove_all,
    prove_plan,
    run_executable_probes,
    run_rules,
    selection_spec_for,
    trace_entry,
)
from repro.analysis.datapath import DatapathProofError
from repro.core import seltables
from repro.core.posit import PositFormat
from repro.kernels.posit_div import kernel_datapath_plan, planned_pairs


# ----------------------------------------------------------- datapath prover


def test_prover_proves_every_plan():
    """Every (format, variant) the kernel datapath accepts is PROVEN: the
    full Table IV x posit8/16/32/64 grid minus the derived posit64-scaled
    exclusion, with exact (Fraction) margins >= 0 on every check."""
    report = prove_all()  # raises DatapathProofError on any violation
    assert report["violations"] == 0
    assert report["proven"] == len(list(planned_pairs()))
    assert report["proven"] >= 35
    skipped = {(s["format"], s["variant"]) for s in report["skipped"]}
    assert skipped == {("posit64", "srt_r4_scaled")}
    # margins are exact rationals; the binding ones sit at exactly 0
    assert report["tightest_margin"] == "0"


def test_every_variant_has_selection_spec():
    from repro.core.divider import VARIANTS

    for variant in VARIANTS:
        spec = selection_spec_for(variant)
        assert check_selection_containment(spec).ok, variant


def test_tampered_threshold_refuted():
    """One m_k moved ONE ulp down must violate containment (the derivation
    takes the ceil of the feasible range, so the floor is tight)."""
    bad = [dict(r) for r in seltables.RADIX4_TABLE]
    bad[3][1] -= 1
    res = check_selection_containment(
        selection_spec_for("srt_r4_cs_of_fr", table=bad))
    assert not res.ok
    assert "VIOLATED" in res.detail and "digit +1" in res.detail
    assert res.margin < 0


def test_tampered_threshold_up_refuted():
    """...and one ulp UP must break the upper bound of the digit below."""
    bad = [dict(r) for r in seltables.RADIX4_TABLE]
    bad[0][2] += 1
    res = check_selection_containment(
        selection_spec_for("srt_r4_cs_of_fr", table=bad))
    assert not res.ok


def test_guard_bit_deficit_refuted():
    """A scaled plan squeezed to one guard bit fewer than Table I needs
    must fail the residual-frame check with a message naming the deficit."""
    plan = kernel_datapath_plan(PositFormat(30), "srt_r4_scaled")
    assert plan is not None and plan.shift == 3
    bad = dataclasses.replace(plan, frac=plan.frac + 1, shift=plan.shift - 1)
    res = check_residual_frame(bad)
    assert not res.ok
    assert "guard bits" in res.detail and "scaled" in res.detail


def test_inconsistent_shift_refuted():
    plan = kernel_datapath_plan(PositFormat(16), "srt_r4_cs_of_fr")
    res = check_residual_frame(dataclasses.replace(plan, shift=plan.shift - 1))
    assert not res.ok
    assert "inconsistent" in res.detail


def test_short_iteration_count_refuted():
    plan = kernel_datapath_plan(PositFormat(16), "srt_r4_cs_of_fr")
    bad = dataclasses.replace(plan, iterations=plan.iterations - 1,
                              fp=plan.fp - 2)
    res = check_otf_width(bad)
    assert not res.ok
    assert "Eq 30/31" in res.detail


def test_prove_plan_collects_unproven():
    plan = kernel_datapath_plan(PositFormat(30), "srt_r4_scaled")
    bad = dataclasses.replace(plan, frac=plan.frac + 1, shift=plan.shift - 1)
    verdict = prove_plan(bad)
    assert not verdict.proven
    assert any(not c.ok for c in verdict.checks)
    j = verdict.as_json()
    assert j["proven"] is False and j["variant"] == "srt_r4_scaled"


def test_prove_all_raises_on_violation(monkeypatch):
    """prove_all with raise_on_violation surfaces the failing constraint."""
    import repro.analysis.datapath as D

    plan = kernel_datapath_plan(PositFormat(30), "srt_r4_scaled")
    bad = dataclasses.replace(plan, frac=plan.frac + 1, shift=plan.shift - 1)
    monkeypatch.setattr(
        D, "planned_pairs",
        lambda formats=None: iter([(PositFormat(30), bad.variant, bad)]))
    with pytest.raises(DatapathProofError, match="guard bits"):
        D.prove_all(formats=())


def test_rewired_table_verification():
    """The legacy entry point now runs the exact check (satellite #1)."""
    seltables.verify_radix4_table_exhaustive()
    seltables.verify_radix4_table_exhaustive(steps=32)  # legacy arg ignored


# ----------------------------------------------------------- jaxpr linter


@pytest.fixture(scope="module")
def entries():
    return build_traced_entries()


def test_real_entries_clean(entries):
    assert run_rules(entries, DEFAULT_RULES) == []


def test_entry_coverage(entries):
    names = {e.name for e in entries}
    assert "smollm-360m/decode_step+health" in names
    assert "smollm-360m/decode_step" in names
    assert "smollm-360m/prefill" in names
    assert "posit_softmax/fused" in names
    assert "posit_router_norm/emulate" in names
    assert "posit_flash_attention/bwd" in names


def test_f64_leak_flagged():
    with jax.enable_x64(True):
        e = trace_entry(
            "leaky", lambda x: x.astype(jnp.float64) * 2.0,
            (jax.ShapeDtypeStruct((4,), jnp.float32),), tags=())
    v = run_rules([e], DEFAULT_RULES)
    assert v and all(x.rule == "no-f64" for x in v)
    assert "float64" in v[0].detail


def test_score_materialization_flagged():
    def toy(q, k):
        return jax.nn.softmax(q @ k.T, axis=-1).sum()

    shp = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    e = trace_entry("toy-attn", jax.grad(toy), (shp, shp),
                    tags=("attention-backward",), params={"big": 200})
    v = run_rules([e], DEFAULT_RULES)
    assert any(x.rule == "no-score-materialization" for x in v)
    assert "[256, 256]" in v[0].detail


def test_posit_datapath_reduce_sum_flagged():
    e = trace_entry("free-order",
                    lambda x: x / x.sum(-1, keepdims=True),
                    (jax.ShapeDtypeStruct((8, 16), jnp.float32),),
                    tags=("posit-datapath",))
    v = run_rules([e], DEFAULT_RULES)
    assert [x.rule for x in v] == ["fixed-order-reductions"]
    assert "fixed_order_rowsum" in v[0].detail


def test_host_callback_flagged():
    def printy(x):
        jax.debug.print("x={}", x.sum())
        return x * 2

    e = trace_entry("printy", printy,
                    (jax.ShapeDtypeStruct((4,), jnp.float32),),
                    tags=("serve-hot-path",))
    v = run_rules([e], DEFAULT_RULES)
    assert [x.rule for x in v] == ["no-host-callback"]


# ----------------------------------------------------------- AST source lint


def test_kernel_sources_clean():
    assert lint_kernel_sources() == []


def test_bad_kernel_source_flagged(tmp_path):
    (tmp_path / "bad.py").write_text(textwrap.dedent("""
        from jax.experimental import pallas as pl

        def launch(x, interpret=False):
            return pl.pallas_call(kern, out_shape=x)(x)
    """))
    v = lint_kernel_sources(tmp_path)
    rules = [x.rule for x in v]
    assert rules == ["pallas-call-discipline"] * 3
    details = " | ".join(x.detail for x in v)
    assert "interpret" in details
    assert "compiler_params" in details
    assert "vmem_limit_bytes" in details
    assert all(x.entry.startswith("bad.py:") for x in v)


# ----------------------------------------------------------- executable probe


def test_one_decode_executable_probe():
    """The dense/emulate probe serves the heterogeneous stream and must
    see exactly one compiled decode executable (fast subset; the CLI/CI
    run covers every family x backend)."""
    assert run_executable_probes(fast=True) == []
