"""The traffic generator: deterministic in the seed, the same spread of
sizes for every seed, and only the shapes a cell can use warmed."""

import numpy as np

from chipbench import traffic as T

CHAT = {"clients": 16, "slots": 16, "buckets": [128, 256],
        "prompt_tokens": {"log_uniform": [64, 256]},
        "output_tokens": {"log_uniform": [128, 512]},
        "calibration_tokens": [2, 64]}


def _draw(seed, n=40, vocab=151936):
    loop = T.ClosedLoop(CHAT, vocab, seed)
    return [loop.next(i % 16) for i in range(n)]


def test_same_seed_same_requests():
    a, b = _draw(2 ** 33 + 7), _draw(2 ** 33 + 7)
    for x, y in zip(a, b):
        assert (x.uid, x.max_new, x.client) == (y.uid, y.max_new, y.client)
        np.testing.assert_array_equal(x.prompt, y.prompt)
    np.testing.assert_array_equal(
        T.calibration_tokens(CHAT, 1000, 5), T.calibration_tokens(CHAT, 1000, 5))


def test_seeds_differ_in_tokens_and_order_not_in_spread():
    a, b = _draw(1, n=400), _draw(2, n=400)
    assert any(x.prompt.size != y.prompt.size for x, y in zip(a, b))
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    for reqs in (a, b):
        lens = np.array([r.prompt.size for r in reqs])
        outs = np.array([r.max_new for r in reqs])
        assert lens.min() >= 64 and lens.max() <= 256
        assert outs.min() >= 128 and outs.max() <= 512
    # every seed's first 400 requests hold the same amount of work to
    # within a percent: the golden-ratio quantiles cover [0, 1) evenly
    work = [sum(r.prompt.size + r.max_new for r in reqs) for reqs in (a, b)]
    assert abs(work[0] - work[1]) / work[0] < 0.01


def test_large_seeds_are_not_truncated():
    assert T.jax_seed(2 ** 40, 10) != T.jax_seed(0, 10)
    assert T.jax_seed(2 ** 40, 10) != T.jax_seed(2 ** 40 + 1, 10)
    assert 0 <= T.jax_seed(2 ** 62 + 5, 11) < 2 ** 31


def test_warm_shapes_are_the_cell_s_own():
    assert list(T.warm_shapes(CHAT)) == [
        (b, g) for b in (128, 256) for g in (1, 2, 4, 8, 16)]
    long = dict(CHAT, clients=4, slots=4, buckets=[512, 1024, 2048],
                prompt_tokens={"log_uniform": [512, 2048]})
    assert list(T.warm_shapes(long)) == [
        (b, g) for b in (512, 1024, 2048) for g in (1, 2, 4)]
    # a bucket no prompt can reach is never warmed
    short = dict(CHAT, prompt_tokens={"log_uniform": [8, 100]})
    assert {b for b, _ in T.warm_shapes(short)} == {128}
