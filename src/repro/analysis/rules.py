"""Concrete lint rules + the traced-entry registry for this repo.

Structural invariants enforced over the jitted entry points of the serve
stack (see :mod:`repro.analysis.jaxpr_lint` for the framework):

  * ``no-f64``                 — no float64/complex128 aval anywhere (the
    TPU datapath is f32/bf16/int32; an f64 leak means someone upcast).
  * ``no-score-materialization`` — the fused flash-attention backward must
    not hold any (Sq, Sk)-shaped intermediate (>= 2 dims >= the block
    threshold): recompute tiles only.
  * ``no-host-callback``       — no ``pure_callback``/``io_callback``/
    ``debug_callback``/``debug_print`` in the serve hot path (each would
    sync the device per decode step).
  * ``fixed-order-reductions`` — no compiler-ordered ``reduce_sum`` on
    posit-datapath entries: every posit-divide denominator must reduce
    through :func:`repro.core.quire.fixed_order_rowsum` (which lowers to a
    ``while`` loop) or the quire routes, so backends/batch compositions
    stay bit-identical.  ``reduce_max`` stays allowed (order-insensitive).
  * ``pallas-call-discipline`` — AST scan over ``src/repro/kernels/``:
    every ``pallas_call`` must pass ``compiler_params``, sit in a function
    exposing a ``vmem_limit_bytes`` parameter, and any ``interpret``
    parameter must default ``None`` (auto: compiled on TPU, interpreter
    elsewhere).
  * ``one-decode-executable``  — executable probe: serving the
    heterogeneous 3-request stream compiles EXACTLY ONE decode executable
    per (family, numerics backend); a retrace means per-slot positions
    leaked into the jit signature.
  * ``packed-warmup-steady-state`` — executable probe: with packed prefill
    enabled, ``ServeEngine.warmup()`` followed by a mixed-length serve
    session must add ZERO new executables across the engine's entire jit
    census (``executable_counts()`` delta == {}): all steady-state pack
    shapes were pre-lowered by warmup, so admission never traces.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .jaxpr_lint import (
    LintRule,
    TracedEntry,
    Violation,
    iter_avals,
    iter_eqns,
    trace_entry,
)

__all__ = [
    "NoF64Rule",
    "NoScoreMaterializationRule",
    "NoHostCallbackRule",
    "FixedOrderReductionRule",
    "DEFAULT_RULES",
    "lint_kernel_sources",
    "build_traced_entries",
    "run_executable_probes",
    "EXECUTABLE_PROBES",
    "run_packed_warmup_probes",
    "PACKED_WARMUP_PROBES",
    "run_sharded_probes",
    "SHARDED_PROBES",
    "DECODE_COLLECTIVE_ALLOWLIST",
    "decode_collective_violations",
]


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------


class NoF64Rule(LintRule):
    name = "no-f64"
    requires_tag = None
    _BAD = ("float64", "complex128")

    def check(self, entry: TracedEntry) -> List[Violation]:
        seen = set()
        for prim, aval in iter_avals(entry.closed):
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt.name in self._BAD:
                key = (prim, dt.name, getattr(aval, "shape", ()))
                if key not in seen:
                    seen.add(key)
        return [Violation(
            self.name, entry.name,
            f"{dt} aval of shape {list(shape)} produced by primitive "
            f"{prim!r}; the datapath is f32/bf16/int32 — find the upcast "
            "(x64 mode or a python float promoted)")
            for prim, dt, shape in sorted(seen, key=str)]


class NoScoreMaterializationRule(LintRule):
    name = "no-score-materialization"
    requires_tag = "attention-backward"

    def check(self, entry: TracedEntry) -> List[Violation]:
        big = entry.params.get("big", 200)
        out: List[Violation] = []
        seen = set()
        for prim, aval in iter_avals(entry.closed):
            shape = tuple(getattr(aval, "shape", ()))
            if sum(1 for d in shape if d >= big) >= 2:
                key = (prim, shape)
                if key in seen:
                    continue
                seen.add(key)
                out.append(Violation(
                    self.name, entry.name,
                    f"(Sq, Sk)-sized intermediate {list(shape)} (>= 2 dims "
                    f">= {big}) produced by {prim!r}: the flash backward "
                    "must recompute block tiles, never hold the full score "
                    "tensor"))
        return out


class NoHostCallbackRule(LintRule):
    name = "no-host-callback"
    requires_tag = "serve-hot-path"
    _PRIMS = frozenset({
        "pure_callback", "io_callback", "debug_callback", "debug_print",
        "callback", "outside_call", "host_callback_call",
    })

    def check(self, entry: TracedEntry) -> List[Violation]:
        out: List[Violation] = []
        for eqn in iter_eqns(entry.closed):
            prim = getattr(eqn.primitive, "name", str(eqn.primitive))
            if prim in self._PRIMS:
                out.append(Violation(
                    self.name, entry.name,
                    f"host callback primitive {prim!r} in a serve hot-path "
                    "entry: each call syncs device->host per decode step; "
                    "move it out of the jitted step (e.g. ride the packed "
                    "(B, 2) token/health transfer)"))
        return out


class FixedOrderReductionRule(LintRule):
    name = "fixed-order-reductions"
    requires_tag = "posit-datapath"

    def check(self, entry: TracedEntry) -> List[Violation]:
        out: List[Violation] = []
        for eqn in iter_eqns(entry.closed):
            prim = getattr(eqn.primitive, "name", str(eqn.primitive))
            if prim == "reduce_sum":
                shapes = [tuple(getattr(v.aval, "shape", ()))
                          for v in eqn.invars if hasattr(v, "aval")]
                out.append(Violation(
                    self.name, entry.name,
                    f"compiler-ordered reduce_sum over {shapes} on a "
                    "posit-datapath entry: denominators feeding the posit "
                    "divider must use core.quire.fixed_order_rowsum (or a "
                    "quire route) so backends and batch compositions stay "
                    "bit-identical"))
        return out


DEFAULT_RULES: Tuple[LintRule, ...] = (
    NoF64Rule(),
    NoScoreMaterializationRule(),
    NoHostCallbackRule(),
    FixedOrderReductionRule(),
)


# ---------------------------------------------------------------------------
# AST rule: pallas_call discipline over src/repro/kernels/
# ---------------------------------------------------------------------------


def _fn_arg_names(fn: ast.FunctionDef) -> set:
    a = fn.args
    return {x.arg for x in
            list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)}


def _interpret_default_violations(fn: ast.FunctionDef,
                                  fname: str) -> List[Violation]:
    out: List[Violation] = []
    a = fn.args
    pos = list(a.posonlyargs) + list(a.args)
    pairs = list(zip(pos[len(pos) - len(a.defaults):], a.defaults))
    pairs += [(arg, d) for arg, d in zip(a.kwonlyargs, a.kw_defaults)
              if d is not None]
    for arg, default in pairs:
        if arg.arg != "interpret":
            continue
        is_none = isinstance(default, ast.Constant) and default.value is None
        if not is_none:
            out.append(Violation(
                "pallas-call-discipline", f"{fname}:{fn.lineno}",
                f"function {fn.name!r}: parameter 'interpret' must default "
                "to None (resolve_interpret auto-selects: compiled on TPU, "
                "interpreter elsewhere) — a hard-coded default either "
                "breaks TPU perf or breaks CPU tests"))
    return out


class _KernelSourceVisitor(ast.NodeVisitor):
    def __init__(self, fname: str):
        self.fname = fname
        self.stack: List[ast.FunctionDef] = []
        self.violations: List[Violation] = []

    def _visit_fn(self, node):
        self.violations.extend(
            _interpret_default_violations(node, self.fname))
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_Call(self, node: ast.Call):
        callee = node.func
        name = callee.attr if isinstance(callee, ast.Attribute) else \
            callee.id if isinstance(callee, ast.Name) else None
        if name == "pallas_call":
            where = f"{self.fname}:{node.lineno}"
            kwargs = {kw.arg for kw in node.keywords}
            if "compiler_params" not in kwargs:
                self.violations.append(Violation(
                    "pallas-call-discipline", where,
                    "pallas_call without compiler_params: every kernel "
                    "launch must bound VMEM via CompilerParams("
                    "vmem_limit_bytes=...)"))
            encl = self.stack[-1] if self.stack else None
            if encl is None or "vmem_limit_bytes" not in _fn_arg_names(encl):
                fn = encl.name if encl is not None else "<module level>"
                self.violations.append(Violation(
                    "pallas-call-discipline", where,
                    f"pallas_call inside {fn!r} which exposes no "
                    "'vmem_limit_bytes' parameter: callers must be able to "
                    "bound the kernel's VMEM footprint"))
        self.generic_visit(node)


def lint_kernel_sources(root: Optional[str] = None) -> List[Violation]:
    """AST-scan every module in ``src/repro/kernels/`` for pallas_call
    discipline.  ``root`` overrides the directory (fixture hook)."""
    if root is None:
        import repro.kernels

        root = Path(repro.kernels.__file__).parent
    root = Path(root)
    out: List[Violation] = []
    for py in sorted(root.glob("*.py")):
        tree = ast.parse(py.read_text(), filename=str(py))
        visitor = _KernelSourceVisitor(py.name)
        visitor.visit(tree)
        out.extend(visitor.violations)
    return out


# ---------------------------------------------------------------------------
# traced-entry registry
# ---------------------------------------------------------------------------


def _numerics(backend: str):
    from repro.numerics.formats import NumericsConfig

    return NumericsConfig(posit_division=True, div_backend=backend)


def _model_entries(arch: str) -> List[TracedEntry]:
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
    B, S = 2, 64
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    out = []
    for health in (True, False):
        out.append(trace_entry(
            f"{arch}/decode_step" + ("+health" if health else ""),
            lambda p, c, t, i, s, _h=health: T.decode_step(
                p, cfg, c, t, i, s, with_health=_h),
            (params, cache, tok, vec, vec), tags=("serve-hot-path",)))
    P = 16
    mini = jax.eval_shape(lambda: T.init_cache(cfg, 1, P))
    toks = jax.ShapeDtypeStruct((1, P), jnp.int32)
    st = jax.ShapeDtypeStruct((1,), jnp.int32)
    out.append(trace_entry(
        f"{arch}/prefill",
        lambda p, c, t, s: T.prefill(p, cfg, {"tokens": t}, c, s),
        (params, mini, toks, st), tags=("serve-hot-path",)))
    return out


def _numerics_entries() -> List[TracedEntry]:
    from repro.numerics.posit_ops import (
        posit_div_values,
        posit_router_norm,
        posit_softmax,
    )

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    col = jax.ShapeDtypeStruct((8, 1), jnp.float32)
    out = []
    for backend in ("emulate", "fused"):
        ncfg = _numerics(backend)
        out.append(trace_entry(
            f"posit_softmax/{backend}",
            lambda v, _c=ncfg: posit_softmax(v, _c),
            (x,), tags=("posit-datapath",)))
        out.append(trace_entry(
            f"posit_router_norm/{backend}",
            lambda v, _c=ncfg: posit_router_norm(v, _c),
            (x,), tags=("posit-datapath",)))
        out.append(trace_entry(
            f"posit_div_values/{backend}",
            lambda a, b, _c=ncfg: posit_div_values(a, b, _c),
            (x, col), tags=("posit-datapath",)))
    return out


def _flash_entries() -> List[TracedEntry]:
    from repro.kernels.posit_flash_attn import posit_flash_attention_ste

    S, big = 256, 200  # kernel blocks are 128: any (>=200, >=200) aval is
    #                    a full score tensor, never a tile
    q = jax.ShapeDtypeStruct((1, S, 2, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, S, 1, 32), jnp.float32)

    def fwd(q, k, v):
        return posit_flash_attention_ste(16, "srt_r4_cs_of_fr", True, 0, 0,
                                         0.0, q, k, v, "fused")

    def loss(q, k, v):
        return fwd(q, k, v).sum()

    return [
        trace_entry("posit_flash_attention/fwd", fwd, (q, kv, kv), tags=()),
        trace_entry("posit_flash_attention/bwd",
                    jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv),
                    tags=("attention-backward",), params={"big": big}),
    ]


def build_traced_entries(
        families: Sequence[str] = ("smollm-360m",)) -> List[TracedEntry]:
    """Every jitted entry point the linter covers: model decode (with and
    without the health probe) + prefill per family, the posit-datapath
    numerics ops on both backends, and the fused flash attention forward
    and backward."""
    entries: List[TracedEntry] = []
    for arch in families:
        entries.extend(_model_entries(arch))
    entries.extend(_numerics_entries())
    entries.extend(_flash_entries())
    return entries


# ---------------------------------------------------------------------------
# executable probes: one decode executable per (family, backend)
# ---------------------------------------------------------------------------

# the same 3-request heterogeneous stream tests/test_serve.py pins: request
# 1's small budget frees its slot mid-flight so request 2 is admitted next
# to a still-decoding slot at a different offset — the retrace trap.
_STREAM: Tuple[Tuple[np.ndarray, int], ...] = (
    (np.array([3, 5, 7], np.int32), 6),
    (np.array([11, 13, 2, 9, 4, 6, 8], np.int32), 2),
    (np.array([17, 19, 23], np.int32), 4),
)

# (probe name, arch, fused numerics) — one representative per family plus
# the dense fused-numerics stack.
EXECUTABLE_PROBES: Tuple[Tuple[str, str, bool], ...] = (
    ("dense/emulate", "smollm-360m", False),
    ("moe/emulate", "olmoe-1b-7b", False),
    ("ssm/emulate", "mamba2-2.7b", False),
    ("hybrid/emulate", "recurrentgemma-2b", False),
    ("dense/fused", "smollm-360m", True),
)


def run_executable_probes(
        probes: Optional[Iterable[Tuple[str, str, bool]]] = None,
        fast: bool = False) -> List[Violation]:
    """Serve the heterogeneous stream per probe; exactly ONE decode
    executable may be compiled.  ``fast`` keeps only the first probe
    (dense/emulate)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, ServeConfig, ServeEngine

    probes = tuple(EXECUTABLE_PROBES if probes is None else probes)
    if fast:
        probes = probes[:1]
    out: List[Violation] = []
    for name, arch, fused in probes:
        cfg = get_config(arch, smoke=True, fused=fused)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))
        before = eng._decode._cache_size()
        eng.serve([Request(p, max_new=m) for p, m in _STREAM])
        n = eng._decode._cache_size() - before
        if n != 1:
            out.append(Violation(
                "one-decode-executable", name,
                f"serving the heterogeneous stream compiled {n} decode "
                "executables (expected exactly 1): per-slot positions or "
                "shapes leaked into the jit signature and every admission "
                "will retrace"))
    return out


# ---------------------------------------------------------------------------
# executable probes: packed warmup covers every steady-state pack shape
# ---------------------------------------------------------------------------

# (probe name, kv_layout) — both cache layouts route packed admission
# through different executables (segment-scatter vs pool-scatter), so both
# must be warmed independently.
PACKED_WARMUP_PROBES: Tuple[Tuple[str, str], ...] = (
    ("packed/dense-kv", "dense"),
    ("packed/paged-kv", "paged"),
)


def run_packed_warmup_probes(
        probes: Optional[Iterable[Tuple[str, str]]] = None,
        fast: bool = False) -> List[Violation]:
    """With ``packed_prefill=True``, ``warmup()`` must pre-lower every
    executable a steady-state mixed-length serve session can hit: the
    ``executable_counts()`` census taken right after warmup must be
    UNCHANGED after serving the heterogeneous stream.  ``fast`` keeps only
    the dense-layout probe."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import Request, ServeConfig, ServeEngine

    probes = tuple(PACKED_WARMUP_PROBES if probes is None else probes)
    if fast:
        probes = probes[:1]
    out: List[Violation] = []
    for name, layout in probes:
        cfg = get_config("smollm-360m", smoke=True)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, ServeConfig(
            max_batch=2, max_seq=64, kv_layout=layout, packed_prefill=True))
        before = eng.warmup()
        eng.serve([Request(p, max_new=m) for p, m in _STREAM])
        after = eng.executable_counts()
        if before != after:
            grew = {k: (before.get(k, 0), after[k])
                    for k in after if after[k] != before.get(k, 0)}
            out.append(Violation(
                "packed-warmup-steady-state", name,
                "serving the heterogeneous stream after warmup() compiled "
                f"new executables: {grew} — a steady-state pack shape "
                "escaped the warmup bucket enumeration and admission will "
                "retrace in production"))
    return out


# ---------------------------------------------------------------------------
# sharded-serving probes: steady layouts, zero retrace, exact collectives
# ---------------------------------------------------------------------------

#: every cross-shard communication primitive the walker recognizes
_COLLECTIVE_PRIMS = frozenset({
    "psum", "all_reduce", "all_gather", "all_to_all", "ppermute",
    "pmax", "pmin", "reduce_scatter", "psum_scatter",
    "sharding_constraint", "reshard",
})

#: the ONLY collectives allowed in the sharded decode hot path.  Every
#: cross-shard combine in models/layers is an exact all-gather (fixed-order
#: group sums, embed owner-select, logits concat are pure data movement +
#: replicated arithmetic) — a psum/reduce_scatter here would reintroduce a
#: TP-degree-dependent reduction order and break bit-identity; a
#: sharding_constraint/reshard would mean a layout escaped the engine's
#: precomputed specs.
DECODE_COLLECTIVE_ALLOWLIST = frozenset({"all_gather"})


def decode_collective_violations(eng, name: str = "decode",
                                 allow=DECODE_COLLECTIVE_ALLOWLIST
                                 ) -> List[Violation]:
    """Walk the sharded engine's decode jaxpr; any communication primitive
    outside ``allow`` is a violation (see DECODE_COLLECTIVE_ALLOWLIST)."""
    import collections as _c

    counts: _c.Counter = _c.Counter()
    for eqn in iter_eqns(eng.decode_jaxpr()):
        prim = getattr(eqn.primitive, "name", str(eqn.primitive))
        if prim in _COLLECTIVE_PRIMS and prim not in allow:
            counts[prim] += 1
    return [Violation(
        "decode-collective-lint", name,
        f"decode hot path contains {n} {prim!r} op(s); only "
        f"{sorted(allow)} are allowed — a reduction collective makes "
        "token bits depend on the TP degree, a reshard means a layout "
        "escaped the engine's precomputed specs")
        for prim, n in sorted(counts.items())]


# (probe name, kv_layout) — both cache layouts run the sharded decode path
# through different executables, so both are probed.
SHARDED_PROBES: Tuple[Tuple[str, str], ...] = (
    ("sharded/dense-kv", "dense"),
    ("sharded/paged-kv", "paged"),
)


def run_sharded_probes(
        probes: Optional[Iterable[Tuple[str, str]]] = None,
        fast: bool = False, tp: int = 2) -> List[Violation]:
    """Sharded-engine extension of the steady-state probes (PR 10).

    For each probe a TP-sharded engine (``tp`` devices, one replica) is
    warmed up and then serves the heterogeneous stream; three invariants
    are enforced per replica:

      * ``sharded-steady-state``   — the post-warmup ``executable_counts``
        census is UNCHANGED by serving (zero recompilation per replica);
      * ``steady-layouts``         — every param/cache leaf still carries
        the sharding precomputed at engine construction (no implicit
        resharding entered the hot loop);
      * ``decode-collective-lint`` — the decode jaxpr contains no
        communication primitive outside the exact-all-gather allowlist.

    Needs >= ``tp`` devices (the CI ``multi-device`` job forces 8 host
    devices via XLA_FLAGS); returns [] — skipped, not failed — below that.
    """
    if jax.device_count() < tp:
        return []
    from repro.configs import get_config
    from repro.launch.mesh import serve_meshes
    from repro.models import transformer as T
    from repro.serve import Request, ServeConfig, ServeEngine

    probes = tuple(SHARDED_PROBES if probes is None else probes)
    if fast:
        probes = probes[:1]
    out: List[Violation] = []
    for name, layout in probes:
        # smoke smollm has 3 heads; resize to a TP-divisible head layout
        # (tp_groups pins the contraction order for bit-identity)
        cfg = get_config("smollm-360m", smoke=True).replace(
            n_heads=4, n_kv_heads=2, head_dim=32, tp_groups=tp)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        mesh = serve_meshes(tp, 1)[0]
        # packed_prefill, like the packed-warmup probes: the paged SOLO
        # path deliberately keys prefill on the raw (plen, t0) pair (see
        # ServeEngine._plan) which no finite warmup can enumerate
        eng = ServeEngine(cfg, params,
                          ServeConfig(max_batch=2, max_seq=64,
                                      kv_layout=layout,
                                      packed_prefill=True), mesh=mesh)
        before = eng.warmup()
        eng.serve([Request(p, max_new=m) for p, m in _STREAM])
        after = eng.executable_counts()
        if before != after:
            grew = {k: (before.get(k, 0), after[k])
                    for k in after if after[k] != before.get(k, 0)}
            out.append(Violation(
                "sharded-steady-state", name,
                f"post-warmup serve compiled new executables on the tp={tp} "
                f"engine: {grew} — a sharded shape escaped warmup and every "
                "replica will retrace in production"))
        for v in eng.steady_layout_violations():
            out.append(Violation("steady-layouts", name, v))
        out.extend(decode_collective_violations(eng, name))
    return out
