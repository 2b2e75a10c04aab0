"""Reduction of a profiler trace to device busy time, executable and kernel
times, and idle gaps labelled by the harness's own spans.

A trace is first flattened into events ``[plane, line, name, start_ns,
dur_ns]`` (``load``), keeping only the device's ``XLA Modules`` and ``XLA
Ops`` lines and the harness's ``bench.*`` host spans; everything below
works on that list, so a recorded trace can be checked without a chip.

On a TPU the profiler names a module execution ``<jit name>(<program
id>)`` and an op by its HLO text, ``%<op name> = <shape> <opcode>(...)``;
a Pallas kernel is a ``custom-call`` named after the jitted function that
launched it (``posit_flash_attention``, ``posit_fused_div_rowwise_pallas``).
The layer scan of a model step is one ``while`` op around the ops of its
body, so container ops (``while``, ``conditional``, ``call``) count for
busy time but not as ops of their own.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import re
from typing import Dict, List, Optional, Tuple

DEVICE_LINES = ("XLA Modules", "XLA Ops")
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)?\s*=\s*(.*)$", re.S)


def load(path: str) -> List[list]:
    """Events of an ``.xplane.pb`` (or of a flattened ``.json.gz``)."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as fh:
            return json.load(fh)
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for e in line.events:
                if host and not e.name.startswith("bench."):
                    continue
                out.append([plane.name, line.name if device else "host",
                            e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(name: str) -> Tuple[str, str]:
    """(op name without its numeric suffix, opcode) of an XLA Ops event."""
    m = _OP.match(name)
    if not m:
        return name, ""
    rhs = m.group(2)
    # the opcode is the word before the operand list, after the shape
    opm = re.search(r"\s([a-z][\w\-]*)\(", rhs)
    return m.group(1), (opm.group(1) if opm else "")


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Reduced:
    """Everything the per-layer readers take from one trace."""

    def __init__(self, events: List[list], window: Optional[Tuple[float, float]] = None):
        host = [e for e in events if e[1] == "host"]
        if window is None:
            spans = [e for e in host if e[2] == "bench.window"]
            if spans:
                window = (spans[0][3], spans[0][3] + spans[0][4])
            else:
                dev = [e for e in events if e[1] in DEVICE_LINES]
                window = (min(e[3] for e in dev),
                          max(e[3] + e[4] for e in dev))
        self.t0, self.t1 = window
        clip = lambda e: (max(e[3], self.t0), min(e[3] + e[4], self.t1))  # noqa: E731
        inside = lambda e: e[3] < self.t1 and e[3] + e[4] > self.t0  # noqa: E731
        self.host = [e for e in host if e[2] != "bench.window" and inside(e)]
        self.modules = sorted((e for e in events
                               if e[1] == "XLA Modules" and inside(e)),
                              key=lambda e: e[3])
        self.ops = sorted((e for e in events if e[1] == "XLA Ops" and inside(e)),
                          key=lambda e: e[3])
        self.busy = _merge([clip(e) for e in self.ops + self.modules])
        self._clip = clip

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy) / 1e9

    def module_ops(self) -> List[Tuple[list, List[list]]]:
        """Each module execution with the ops that ran inside it."""
        out, i = [], 0
        for m in self.modules:
            m0, m1 = m[3], m[3] + m[4]
            while i < len(self.ops) and self.ops[i][3] < m0:
                i += 1
            j = i
            inner = []
            while j < len(self.ops) and self.ops[j][3] < m1:
                inner.append(self.ops[j])
                j += 1
            out.append((m, inner))
        return out

    def op_seconds(self, ops: List[list]) -> Dict[str, float]:
        """Seconds per op name inside the window, container ops left out."""
        agg: Dict[str, float] = collections.defaultdict(float)
        for e in ops:
            name, code = op_name(e[2])
            if code in CONTAINERS:
                continue
            a, b = self._clip(e)
            agg[name] += max(0.0, b - a) / 1e9
        return dict(agg)

    def top_ops(self, n: int = 10) -> List[list]:
        agg = self.op_seconds(self.ops)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle device seconds per harness span (the innermost ``bench.*``
        span around each gap's midpoint; ``none`` when no span covers it),
        the largest first."""
        edges = [self.t0] + [x for iv in self.busy for x in iv] + [self.t1]
        agg: Dict[str, float] = collections.defaultdict(float)
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            covering = [e for e in self.host if e[3] <= mid <= e[3] + e[4]]
            label = min(covering, key=lambda e: e[4])[2] if covering else "none"
            agg[label] += (b - a) / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def is_attention(op: list) -> bool:
    """A Pallas attention kernel call (the jitted wrapper's name)."""
    name, code = op_name(op[2])
    return code == "custom-call" and "attention" in name


def model_steps(red: Reduced, decode_steps: int) -> Optional[Dict[str, list]]:
    """Split the model-step executions (modules that run an attention
    kernel) into decode and prefill.  The program does not name its
    executables, so the decode executable is told apart by its count: it
    is the one program that ran as many times as the engine counted decode
    steps in the traced span; every other model step is a prefill."""
    by_prog: Dict[str, list] = collections.defaultdict(list)
    for m, ops in red.module_ops():
        if any(is_attention(o) for o in ops):
            by_prog[m[2]].append((m, ops))
    if not by_prog:
        return None
    dec = min(by_prog, key=lambda k: (abs(len(by_prog[k]) - decode_steps),
                                      -len(by_prog[k])))
    return {"decode": by_prog[dec] if decode_steps else [],
            "prefill": [x for k, v in by_prog.items() if k != dec or not decode_steps
                        for x in v]}


def seconds_of(red: Reduced, execs: list, match=None) -> float:
    """Device seconds of these module executions inside the window, or,
    with ``match``, of the non-container ops in them that ``match``
    accepts."""
    total = 0.0
    for m, ops in execs:
        for e in ([m] if match is None else [o for o in ops if match(o)]):
            a, b = red._clip(e)
            total += max(0.0, b - a) / 1e9
    return total


def record_steps(rec: dict) -> Optional[Dict[str, list]]:
    """``model_steps`` of a run record's trace, or None when the run was
    not traced."""
    tr = rec.get("trace")
    if tr is None:
        return None
    return model_steps(tr["reduced"], tr["decode_steps"])
