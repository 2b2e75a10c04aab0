"""The quotients of every division site of one served request's prefill,
held against the plain posit reference.

The program's own prefill (``transformer.prefill``, the function its
engine compiles for a solo admission) runs again on a request the window
served, at that request's prompt bucket and left pad, with its layers
unrolled so that each site's values can leave the program.  Each site the
configuration's block lists (``blocks.Site``) is wrapped where the model
calls it, so the kernels and bindings are the engine's own, and ``ROWS``
real rows of every call are sampled, evenly spaced:

* a ``"quotient"`` site's quotients are compared bit for bit with
  ``posit_ref.posit_divide`` of the numerator and divisor the block reads
  from the call;
* a ``"grid"`` site divides where its operands cannot be seen (the flash
  kernel's ``acc / l``): its float32 outputs must all be posit values,
  each a rounded posit quotient.
"""

from __future__ import annotations

import importlib

import numpy as np

import posit_ref

ROWS = 64
# what ``compare`` sums for each kind of site: wrong values, values, calls
_KEYS = {"quotient": ("quotient_mismatches", "quotients", "quotient_sites"),
         "grid": ("grid_off", "grid_outputs", "grid_sites")}


def _bits(a: np.ndarray) -> np.ndarray:
    b = np.asarray(a, np.float32).view(np.uint32).copy()
    b[np.isnan(a)] = 0x7FC00000
    return b


def program(cfg, sites):
    """The jitted prefill with ``sites`` wrapped: (params, cache, (1, P)
    tokens, (1,) left pad) -> one list per site of its calls' sampled
    values, as ``capture`` returns them."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    ucfg = cfg.replace(scan_layers=False)
    mods = [importlib.import_module(s.module) for s in sites]

    def fn(params, cache, toks, s0):
        seen = [[] for _ in sites]
        real = [getattr(m, s.attr) for s, m in zip(sites, mods)]

        def pick(a):
            """ROWS real rows of a (1, S, ...) array, evenly spaced."""
            S = a.shape[1]
            if S == 1:
                return a[0]
            return a[0][s0[0] + (jnp.arange(ROWS) * (S - s0[0])) // ROWS]

        def wrap(i):
            site, f = sites[i], real[i]

            def called(*a, **k):
                out = f(*a, **k)
                if site.check == "quotient":
                    num, den = site.operands(*a, **k)
                    seen[i].append((pick(num), pick(den), pick(out)))
                else:
                    seen[i].append(pick(out))
                return out

            return called

        for i, (s, m) in enumerate(zip(sites, mods)):
            setattr(m, s.attr, wrap(i))
        try:
            T.prefill(params, ucfg, {"tokens": toks}, cache, s0)
        finally:
            for s, m, f in zip(sites, mods, real):
                setattr(m, s.attr, f)
        return seen

    return jax.jit(fn)


def capture(cfg, params, tokens: np.ndarray, start: int, max_seq: int,
            sites):
    """Run the prefill of ``tokens`` ((P,) int32, left pad ``start``) and
    return each site's sampled values, one list of calls per site: (num,
    den, quotient) at a ``"quotient"`` site, the output at a ``"grid"``
    site."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    cache = T.init_cache(cfg, 1, max_seq)
    seen = program(cfg, sites)(params, cache, jnp.asarray(tokens[None]),
                               jnp.asarray([start], jnp.int32))
    return jax.tree.map(np.asarray, seen)


def compare(sites, seen, bits: int) -> dict:
    """Quotients that differ from the reference, summed over the quotient
    sites, and outputs that are not posit values, summed over the grid
    sites, with how many calls and values of each were compared, in all
    and site by site."""
    out = {k: 0 for keys in _KEYS.values() for k in keys}
    out["by_site"] = {}
    for site, calls in zip(sites, seen):
        bad = n = 0
        for c in calls:
            if site.check == "quotient":
                num, den, q = c
                want = posit_ref.posit_divide(
                    num, np.broadcast_to(den, num.shape), bits)
                bad += int(np.count_nonzero(_bits(q) != _bits(want)))
            else:
                q = c.astype(np.float64)
                bad += int(np.count_nonzero(posit_ref.round_posit(q, bits) != q))
            n += q.size
        k_bad, k_values, k_sites = _KEYS[site.check]
        out[k_bad] += bad
        out[k_values] += n
        out[k_sites] += len(calls)
        out["by_site"][site.attr] = {"calls": len(calls), "values": n,
                                     "bad": bad}
    return out
