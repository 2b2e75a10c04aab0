"""The llama block's FLOP and byte formulas against hand counts at smoke
size."""

from blocks import llama

M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab": 32}


def _token_flops(n_attended):
    """One token through the model, matmul by matmul."""
    d, F, H, KV, hd, V = 8, 16, 2, 1, 4, 32
    per_layer = (2 * d * H * hd          # q
                 + 2 * 2 * d * KV * hd   # k, v
                 + 2 * H * hd * d        # out projection
                 + 3 * 2 * d * F         # w1, w3, w2
                 + 2 * H * hd * n_attended   # scores
                 + 2 * H * hd * n_attended)  # weighted values
    return 2 * per_layer, 2 * d * V


def test_decode_flops_counts_live_rows_of_active_slots():
    # two active slots with 3 and 5 live rows; parked slots add nothing
    want = 0
    for n in (3, 5):
        layers, head = _token_flops(n)
        want += layers + head
    assert llama.decode_flops(M, [3, 5]) == want


def test_prefill_flops_counts_real_tokens_and_one_logit_row():
    n = 5
    layers = sum(_token_flops(i)[0] for i in range(1, n + 1))
    assert llama.prefill_flops(M, n) == layers + 2 * 8 * 32
    # a 5-token prompt padded to a bucket of 8 still counts 5 tokens
    assert llama.prefill_flops(M, 5) < llama.prefill_flops(M, 8)


def test_flash_decode_bytes_need_not_layout():
    rows = [10, 30]
    want = 2 * sum(2 * n * 1 * 4 * 2 + 2 * 2 * 4 * 2 for n in rows)
    assert llama.flash_decode_bytes(M, rows) == want
    # what a float32, 128-lane, max_seq-row layout would move for the same
    # step is far more, and is not what the formula counts
    max_seq, lanes = 64, 128
    layout = 2 * len(rows) * 2 * max_seq * 1 * lanes * 4
    assert llama.flash_decode_bytes(M, rows) * 10 < layout


def test_rmsnorm_divide_bytes():
    n = 7
    rows = (2 * 2 + 1) * n
    assert llama.rmsnorm_divide_bytes(M, n) == rows * (8 * 4 + 8 * 4 + 4)


def test_smollm_widths():
    m = {"n_layers": 32, "d_model": 960, "n_heads": 15, "n_kv_heads": 5,
         "head_dim": 64, "d_ff": 2560, "vocab": 49152}
    # 32 layers x (0.9216 + 0.6144 ... ) M weights: about 315 M in layers
    assert llama.linear_params(m) == 32 * (960 * 960 * 2 + 2 * 960 * 320 + 3 * 960 * 2560)
