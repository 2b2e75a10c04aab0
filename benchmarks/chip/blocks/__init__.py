"""Per-architecture blocks: everything the benchmark knows of one model
architecture, in one module ``blocks/<name>.py`` that a configuration
file names under ``"block"``.

A block module provides:

* ``make_weights(model, seed)``: the parameter tree in the program's
  layout, made on the device from the seed in one jitted call;
* ``logits(params, model, tokens, q8)``: the plain float32 ``jax.numpy``
  reference of the whole model, (S,) tokens -> (S, vocab) logits,
  importing nothing of the program; ``q8`` rounds every weight and every
  activation that enters a linear layer (the low-precision control), and
  is the identity otherwise;
* the work the per-layer metrics count, from the configuration's
  ``model`` group and what the traced window served:
  ``decode_flops(model, live_rows)``, ``prefill_flops(model,
  prompt_len)``, ``flash_decode_bytes(model, live_rows)`` and
  ``rmsnorm_divide_bytes(model, prompt_len)``, each counting the work the
  model needs (active experts, window-clamped keys), not a layout;
* ``DIVISION_SITES``: a sequence of ``Site``, where the program divides.

``model`` is the ``model`` group of a configuration file.
"""

from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class Site:
    """One division site: the program function ``module.attr``, which the
    model calls through its module, so that the check can wrap it.

    ``check`` is ``"quotient"``: the sampled quotients (the call's output)
    must equal ``posit_ref.posit_divide`` of the numerator and divisor
    that ``operands(*args, **kwargs)`` gives from the call, bit for bit;
    or ``"grid"``: the sampled outputs must be posit values.  Operands and
    outputs are (1, S, ...) arrays with the sequence on axis 1; a divisor
    broadcasts against its numerator."""

    module: str
    attr: str
    check: str
    operands: Optional[Callable] = None


def known() -> list:
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def get(name):
    """The block module called ``name``; an unknown name exits with the
    known ones."""
    if name not in known():
        raise SystemExit(f"unknown block {name!r}; known blocks: {known()}")
    return importlib.import_module(f"{__name__}.{name}")
