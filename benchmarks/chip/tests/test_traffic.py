"""Every seed serves the same work, in another order.

Run by path: ``python -m pytest benchmarks/chip/tests``."""
import numpy as np

from benchmarks.chip import traffic


def test_open_loop_seeds_share_sizes_and_gaps():
    mix = traffic.load("chat-open-lutmu")
    a = traffic.open_loop(mix, 1, 151936, 60.0)
    b = traffic.open_loop(mix, 3_000_000_017, 151936, 60.0)
    assert len(a) == len(b) and len(a) % mix["block"] == 0
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    gaps = [np.diff([0.0] + [r.due_s for r in x]) for x in (a, b)]
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # distinct first tokens: no two prompts share a cached prefix
    assert len({r.prompt[0] for r in a}) == len(a)


def test_closed_loop_seeds_share_sizes():
    mix = traffic.load("batch-closed")
    fa, sa = traffic.closed_loop(mix, 1, 151936, 256)
    fb, sb = traffic.closed_loop(mix, 2, 151936, 256)
    assert len(fa) == len(fb) == mix["clients"]
    assert sorted(r.max_new for r in fa) == sorted(r.max_new for r in fb)
    assert sorted(r.max_new for r in sa) == sorted(r.max_new for r in sb)
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(lo <= r.max_new <= hi for r in sa)
    assert all(1 <= r.max_new <= hi for r in fa)


def test_lengths_follow_the_mix():
    mix = traffic.load("chat-open-lutmu")
    reqs = traffic.open_loop(mix, 5, 151936, 200.0)
    p = np.array([len(r.prompt) for r in reqs])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert 0.5 * mix["prompt"]["median"] < np.median(p) < 2 * mix["prompt"][
        "median"]
