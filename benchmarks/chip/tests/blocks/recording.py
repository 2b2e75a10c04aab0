"""A block for the tests: the llama block, with a record of which of its
parts the harness reached."""

from blocks import llama

CALLS = set()


def _recorded(name):
    real = getattr(llama, name)

    def call(*a, **k):
        CALLS.add(name)
        return real(*a, **k)

    return call


make_weights = _recorded("make_weights")
logits = _recorded("logits")
decode_flops = _recorded("decode_flops")
prefill_flops = _recorded("prefill_flops")
flash_decode_bytes = _recorded("flash_decode_bytes")
rmsnorm_divide_bytes = _recorded("rmsnorm_divide_bytes")


def __getattr__(name):
    # DIVISION_SITES is read, not called: record the read
    if name == "DIVISION_SITES":
        CALLS.add(name)
        return llama.DIVISION_SITES
    raise AttributeError(name)
