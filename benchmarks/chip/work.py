"""The work a dense decoder needs, counted from its sizes.

These count what the model and the attention need, whatever implements
them: real prompt tokens (not bucket padding), active slots (not parked
ones), live KV rows (not ``max_seq``), and the model's ``head_dim`` in the
cache's dtype (not a padded or widened kernel layout).  A faster
implementation of the same work therefore reads as a higher share.

``model`` is the ``model`` group of a configuration file.  A FLOP is one
multiply or one add; a matrix product of (m, k) by (k, n) is 2 m k n.
"""

from __future__ import annotations


def linear_params(model: dict) -> int:
    """Weights that one token multiplies through in the decoder layers."""
    d, F = model["d_model"], model["d_ff"]
    H, KV, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return model["n_layers"] * per_layer


def attention_flops(model: dict, queries: int, keys: int) -> int:
    """Scores and weighted values of ``queries`` rows over ``keys`` rows
    each, in every layer and head."""
    return 4 * model["n_layers"] * model["n_heads"] * model["head_dim"] \
        * queries * keys


def decode_flops(model: dict, live_rows) -> int:
    """One decode step: one token per active slot, slot ``i`` attending
    ``live_rows[i]`` cache rows (its prompt and the tokens so far,
    including the row this step writes), and its logits."""
    lin = 2 * linear_params(model) + 2 * model["d_model"] * model["vocab"]
    return sum(lin + attention_flops(model, 1, int(n)) for n in live_rows)


def prefill_flops(model: dict, prompt_len: int) -> int:
    """One solo prefill of ``prompt_len`` real tokens: every token through
    the layers, causal attention over rows 1..n, and the last position's
    logits, which give the first token."""
    n = int(prompt_len)
    causal_pairs = n * (n + 1) // 2
    return (2 * linear_params(model) * n
            + attention_flops(model, 1, causal_pairs)
            + 2 * model["d_model"] * model["vocab"])


def flash_decode_bytes(model: dict, live_rows, kv_bytes: int = 2,
                       act_bytes: int = 2) -> int:
    """HBM bytes decode attention needs in one step, over every layer: each
    active slot's live K and V rows at ``head_dim``, read once per KV head
    in the cache's dtype (``kv_bytes``), plus its query and output rows."""
    H, KV, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    per_layer = sum(2 * int(n) * KV * hd * kv_bytes + 2 * H * hd * act_bytes
                    for n in live_rows)
    return model["n_layers"] * per_layer


def rmsnorm_divide_bytes(model: dict, prompt_len: int) -> int:
    """HBM bytes of the RMSNorm divides in one prefill of ``prompt_len``
    real tokens: two norms per layer and the final one, each reading a
    float32 numerator row and one float32 divisor per row and writing a
    float32 quotient row."""
    rows = (2 * model["n_layers"] + 1) * int(prompt_len)
    return rows * (2 * 4 * model["d_model"] + 4)
