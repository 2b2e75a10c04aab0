"""The serving engine's ``serve.*`` profiler spans in a trace, and the
five layer numbers they give.

``serve/engine.py`` opens ``serve.admit`` around each solo admission (args
``rid``, ``prompt``, ``bucket``, ``wait_ms``) and ``serve.step`` around
each decode step (args ``step``, ``active``, ``live_rows``,
``read_rows``), with children ``serve.prefill``, ``serve.scatter``,
``serve.first_token``, ``serve.dispatch``, ``serve.sync`` and
``serve.record``.  ``trace_reduce.load`` keeps only the harness's
``bench.*`` host spans; ``load`` here gives the ``serve.*`` ones, with
their args (profiler event stats) as a sixth element, to add to its
events.  ``run.py`` does not call it, so a cell's traced line carries none
of these numbers; ``record_trace.py`` reports them.  A program without
the spans gives no events, and every reader then reads ``None``.
"""

import numpy as np


def load(path: str) -> list:
    """The ``serve.*`` host spans of an ``.xplane.pb``, as ``[plane,
    "host", name, start_ns, dur_ns, args]``."""
    from jax.profiler import ProfileData

    return [[plane.name, "host", e.name, float(e.start_ns),
             float(e.duration_ns), dict(e.stats)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def spans(rec, name):
    """The ``name`` spans that overlap the traced span; empty when the run
    was not traced or the trace holds no such span."""
    tr = rec.get("trace")
    if tr is None:
        return []
    return [e for e in tr["reduced"].host if e[2] == name and len(e) > 5]


def seconds(rec, events):
    """Seconds of these spans inside the traced span."""
    red = rec["trace"]["reduced"]
    return sum(max(0.0, b - a) for a, b in map(red._clip, events)) / 1e9


def admit_wait_p50_ms(rec):
    """Scheduler: how long a request waited for a slot, median ``wait_ms``
    of ``serve.admit`` (engine clock, from submit to the start of its
    admission, so without the prefill that ``queue_wait_p50_ms``
    includes)."""
    waits = [e[5]["wait_ms"] for e in spans(rec, "serve.admit")]
    return float(np.percentile(waits, 50)) if waits else None


def prefill_p50_ms(rec):
    """Model step, prefill: median duration of ``serve.admit`` in ms: the
    prefill and its health read, the cache scatter and the first-token
    sample, which is what admission adds to a request's time to first
    token."""
    durs = [e[4] / 1e6 for e in spans(rec, "serve.admit")]
    return float(np.percentile(durs, 50)) if durs else None


def admit_stall_share(rec):
    """Scheduler: the share of serving time spent admitting, during which
    no slot decodes: seconds in ``serve.admit`` over seconds in
    ``serve.admit`` and ``serve.step``, in %."""
    admit, step = spans(rec, "serve.admit"), spans(rec, "serve.step")
    if not admit and not step:
        return None
    a, s = seconds(rec, admit), seconds(rec, step)
    return 100.0 * a / (a + s) if a + s > 0 else None


def kv_live_share(rec):
    """Kernels, flash decode: the share of the KV rows decode attention
    scans that a slot attends, sum of ``live_rows`` over sum of
    ``read_rows`` of ``serve.step``, in %.  Flash decode reads every
    slot's ``max_seq`` rows whatever its length."""
    steps = spans(rec, "serve.step")
    read_rows = sum(e[5]["read_rows"] for e in steps)
    if not read_rows:
        return None
    return 100.0 * sum(e[5]["live_rows"] for e in steps) / read_rows


def prefill_pad_share(rec):
    """Model step, prefill: the share of the prefilled prompt rows that are
    bucket padding, 1 - sum of ``prompt`` over sum of ``bucket`` of
    ``serve.admit``, in %."""
    admits = spans(rec, "serve.admit")
    rows = sum(e[5]["bucket"] for e in admits)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(e[5]["prompt"] for e in admits) / rows)


READERS = {f.__name__: f for f in (admit_wait_p50_ms, prefill_p50_ms,
                                   admit_stall_share, kv_live_share,
                                   prefill_pad_share)}


def serve_idle_share(red):
    """The share of the traced span's idle device seconds that
    ``idle_gaps`` labels with a ``serve.*`` span, in %."""
    gaps = red.idle_gaps(10 ** 6)
    idle = sum(v for _, v in gaps)
    if not idle:
        return None
    return 100.0 * sum(v for k, v in gaps if k.startswith("serve.")) / idle
