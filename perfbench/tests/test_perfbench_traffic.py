"""Every seed serves the same request sizes in the same order (the seed
draws only token ids), the longest prompt always with the longest output
(so every call has the same block-table width), within the
configuration's position cap."""
import numpy as np
import pytest

import traffic


@pytest.mark.parametrize("name", ["chat", "translate_bulk"])
def test_same_sizes_every_seed(name):
    mix = traffic.load(name)
    vocab = 1000

    def sizes(seed, m=mix):
        if m["kind"] == "bursts":
            reqs = next(traffic.bursts(m, seed, vocab))
        else:
            reqs = traffic.job(m, seed, vocab)
        return [(len(r.prompt), r.max_tokens) for r in reqs]

    a, b = sizes(1), sizes(3_000_000_021)
    assert a == b
    other = sizes(1, dict(mix, layout_seed=mix["layout_seed"] + 1))
    assert other != a
    assert sorted(p for p, _ in a) == sorted(p for p, _ in other)
    assert max(p + o for p, o in a) == sum(traffic.longest(mix))
    assert max(p + o for p, o in b) == sum(traffic.longest(mix))
    if mix["output"]["dist"] != "ratio":
        assert sorted(o for _, o in a) == sorted(o for _, o in other)
    assert len(a) == traffic.rows(mix)
    if mix["kind"] == "job":
        # every block of the job holds the same prompt sizes
        blk = mix["block"]
        first = sorted(p for p, _ in a[:blk])
        assert all(sorted(p for p, _ in a[i:i + blk]) == first
                   for i in range(0, len(a), blk))


def test_tokens_come_from_the_seed():
    mix = traffic.load("chat")
    a = next(traffic.bursts(mix, 7, 500))
    b = next(traffic.bursts(mix, 7, 500))
    c = next(traffic.bursts(mix, 8, 500))
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(len(x.prompt) == len(y.prompt)
                   and np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))
    assert all(1 <= r.prompt.min() and r.prompt.max() < 500 for r in a)


def test_position_cap():
    mix = traffic.load("chat")
    reqs = next(traffic.bursts(mix, 5, 100, 1100))
    assert all(1 <= r.max_tokens and len(r.prompt) + r.max_tokens <= 1100
               for r in reqs)
    assert max(len(r.prompt) + r.max_tokens for r in reqs) == 1100
    assert sum(traffic.longest(mix, 1100)) == 1100
    with pytest.raises(ValueError):
        next(traffic.bursts(mix, 5, 100, 600))
