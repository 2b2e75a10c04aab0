"""Every seed gets the same work on the same schedule, with its own tokens."""

import traffic as TR

MIX = {"loop": "open", "rate_rps": 2.5,
       "prompt": {"dist": "lognormal", "median": 200, "sigma": 1.0, "min": 16, "max": 1024},
       "output": {"dist": "lognormal", "median": 48, "sigma": 0.8, "min": 8, "max": 256},
       "lead_in_s": 10, "shape_seed": 1}


def _window(seed):
    return [r for r in TR.open_loop(MIX, seed, 40, 1000) if r.due >= 0]


def test_same_schedule_for_every_seed():
    a, b = _window(1), _window(2 ** 31 + 5)
    assert len(a) == len(b) == 100
    assert [(r.prompt.size, r.max_new, r.due) for r in a] == \
        [(r.prompt.size, r.max_new, r.due) for r in b]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all(0 <= r.due < 40 for r in a)
    # stratified: the window holds the distribution's quantiles
    p = sorted(r.prompt.size for r in a)
    assert p[0] == 16 and p[-1] == 1024 and 180 <= p[50] <= 220


def test_same_seed_same_requests():
    a, b = _window(7), _window(7)
    assert all((x.prompt == y.prompt).all() and x.due == y.due for x, y in zip(a, b))


def test_buckets_refuse_a_fallback():
    assert TR.buckets(MIX, 2048) == [16, 32, 64, 128, 256, 512, 1024]
    try:
        TR.buckets(MIX, 1100)
    except ValueError:
        return
    raise AssertionError("a bucket that overruns max_seq must be refused")
