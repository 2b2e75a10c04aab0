"""The quotients of every division site of one served request's prefill,
held against the plain posit reference.

The program's own prefill (``transformer.prefill``, the function its
engine compiles for a solo admission) runs again on a request the window
served, at that request's prompt bucket and left pad, with its layers
unrolled so that each site's values can leave the program.  The two
kinds of site a fused configuration divides at are wrapped where the
model calls them, so the kernels and bindings are the engine's own:

* every RMSNorm (``repro.models.layers.posit_rmsnorm_div``: x / rms on
  the rowwise SRT kernel).  The operands and quotients of a sample of
  each site's real rows are compared bit for bit with
  ``posit_ref.posit_divide``;
* every attention (``repro.kernels.posit_flash_attn.posit_flash_attention``,
  whose ``acc / l`` normalizer divides inside the flash kernel).  Its
  float32 outputs at a sample of real query rows must all be posit16
  values: the accumulators never leave the kernel, so what can be held to
  the reference is that each output is a rounded posit quotient.
"""

from __future__ import annotations

import numpy as np

import posit_ref

ROWS = 64


def _bits(a: np.ndarray) -> np.ndarray:
    b = np.asarray(a, np.float32).view(np.uint32).copy()
    b[np.isnan(a)] = 0x7FC00000
    return b


def program(cfg):
    """The jitted prefill with its sites wrapped: (params, cache, (1, P)
    tokens, (1,) left pad) -> (norms, flash) as ``capture`` returns them."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import posit_flash_attn as FA
    from repro.models import layers as L
    from repro.models import transformer as T

    ucfg = cfg.replace(scan_layers=False)

    def fn(params, cache, toks, s0):
        norms, flash = [], []
        real_norm, real_flash = L.posit_rmsnorm_div, FA.posit_flash_attention

        def pick(a):
            """ROWS real rows of a (1, S, ...) site, evenly spaced."""
            S = a.shape[1]
            if S == 1:
                return a[0]
            return a[0][s0[0] + (jnp.arange(ROWS) * (S - s0[0])) // ROWS]

        def norm(x, rms, ncfg):
            q = real_norm(x, rms, ncfg)
            norms.append((pick(x), pick(rms), pick(q)))
            return q

        def attend(*a, **k):
            out = real_flash(*a, **k)
            flash.append(pick(out))
            return out

        L.posit_rmsnorm_div, FA.posit_flash_attention = norm, attend
        try:
            T.prefill(params, ucfg, {"tokens": toks}, cache, s0)
        finally:
            L.posit_rmsnorm_div, FA.posit_flash_attention = real_norm, real_flash
        return norms, flash

    return jax.jit(fn)


def capture(cfg, params, tokens: np.ndarray, start: int, max_seq: int):
    """Run the prefill of ``tokens`` ((P,) int32, left pad ``start``) and
    return each site's sampled values: ``norms``, a list of (x, rms, q)
    per RMSNorm, and ``flash``, a list of outputs per attention."""
    import jax.numpy as jnp

    from repro.models import transformer as T

    cache = T.init_cache(cfg, 1, max_seq)
    norms, flash = program(cfg)(params, cache, jnp.asarray(tokens[None]),
                                jnp.asarray([start], jnp.int32))
    return ([tuple(np.asarray(v) for v in site) for site in norms],
            [np.asarray(o) for o in flash])


def compare(norms, flash, bits: int) -> dict:
    """Mismatches of the RMSNorm quotients against the reference, and
    attention outputs that are not posit values, with how many of each
    were compared."""
    bad = seen = 0
    for x, rms, q in norms:
        want = posit_ref.posit_divide(x, np.broadcast_to(rms, x.shape), bits)
        bad += int(np.count_nonzero(_bits(q) != _bits(want)))
        seen += q.size
    off = total = 0
    for o in flash:
        o = o.astype(np.float64)
        off += int(np.count_nonzero(posit_ref.round_posit(o, bits) != o))
        total += o.size
    return {"norm_mismatches": bad, "norm_quotients": seen,
            "norm_sites": len(norms), "flash_off_grid": off,
            "flash_outputs": total, "flash_sites": len(flash)}
