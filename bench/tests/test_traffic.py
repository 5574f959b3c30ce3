"""Traffic generation (``bench/traffic.py``) from the committed mixes."""
import json

import numpy as np
import pytest

from bench import harness, traffic

MIXES = ["chat-shared", "reasoning-closed"]
VOCAB = 151_936
BIG = 2 ** 31 + 977


def _mix(name):
    return json.loads((harness.BENCH / "traffic" / f"{name}.json")
                      .read_text())


def _flat(s):
    items = s.items if s.loop == "open" else [i for q in s.items for i in q]
    return items + s.warm


def _bytes(s) -> bytes:
    return b"".join(np.float64(i.due_s).tobytes() + i.prompt.tobytes()
                    + np.int64([i.max_new, i.prefix]).tobytes()
                    for i in _flat(s))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(_mix(name), BIG, 45, VOCAB, 17)
    b = traffic.schedule(_mix(name), BIG, 45, VOCAB, 17)
    assert _bytes(a) == _bytes(b)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_schedule_same_work(name):
    """Another seed draws another order of the same sizes, and other
    tokens: each period (the lead, the window) holds the same prompt and
    output lengths, prefix picks and arrival gaps."""
    a = traffic.schedule(_mix(name), BIG, 45, VOCAB, 17)
    b = traffic.schedule(_mix(name), BIG + 1, 45, VOCAB, 17)
    assert _bytes(a) != _bytes(b)
    fa, fb = _flat(a), _flat(b)
    assert len(fa) == len(fb)
    assert [len(i.prompt) for i in fa] != [len(i.prompt) for i in fb]
    assert all(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(fa, fb))

    def periods(s):
        if s.loop != "open":
            return [_flat(s)]
        return [[i for i in s.items if i.due_s < 0],
                [i for i in s.items if i.due_s >= 0]]

    for pa, pb in zip(periods(a), periods(b)):
        for key in (lambda i: len(i.prompt), lambda i: i.max_new,
                    lambda i: i.prefix):
            assert sorted(map(key, pa)) == sorted(map(key, pb))
    if a.loop == "open":
        for pa, pb in zip(periods(a), periods(b)):
            ga = np.diff([i.due_s for i in pa])
            gb = np.diff([i.due_s for i in pb])
            assert not np.allclose(ga, gb)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_their_ranges(name):
    mix = _mix(name)
    s = traffic.schedule(mix, BIG, 45, VOCAB, 17)
    pre = mix.get("prefix", {}).get("tokens", 0)
    items = s.items if s.loop == "open" else [i for q in s.items for i in q]
    for i in items:
        assert mix["output"]["min"] <= i.max_new <= mix["output"]["max"]
        tail = len(i.prompt) - (pre if i.prefix >= 0 else 0)
        assert mix["prompt"]["min"] <= tail <= mix["prompt"]["max"]
        assert i.prompt.dtype == np.int32
        assert 0 <= i.prompt.min() and i.prompt.max() < VOCAB


def test_open_loop_arrivals_and_prefixes():
    mix = _mix("chat-shared")
    lead = mix["lead_s"]
    s = traffic.schedule(mix, BIG, 45, VOCAB, 17)
    due = [i.due_s for i in s.items]
    window = [d for d in due if d >= 0]
    assert len(window) == round(mix["rate_per_s"] * 45)
    assert len(due) - len(window) == round(mix["rate_per_s"] * lead)
    assert due == sorted(due) and -lead < due[0] and due[-1] < 45
    # one warm-up request per shared prefix, each starting with it
    assert [i.prefix for i in s.warm] == list(range(4))
    by_prefix = {i.prefix: i.prompt[:512] for i in s.warm}
    for i in s.items:
        assert np.array_equal(i.prompt[:512], by_prefix[i.prefix])
    # Zipf: each prefix gets its share of the window's requests
    counts = np.bincount([i.prefix for i in s.items if i.due_s >= 0],
                         minlength=4)
    want = traffic.zipf_weights(4, mix["prefix"]["zipf_alpha"]) * len(window)
    assert np.all(np.abs(counts - want) < 1)


def test_lognormal_median_and_clip():
    spec = {"dist": "lognormal", "median": 48, "sigma": 0.7, "min": 16,
            "max": 192}
    x = traffic.lengths(spec, 20_000)
    assert x.min() == 16 and x.max() == 192
    assert abs(np.median(x) - 48) <= 1
    assert list(x) == sorted(x)
    with pytest.raises(ValueError):
        traffic.lengths({**spec, "dist": "uniform"}, 3)


def test_gaps_and_picks():
    g = traffic.gaps(9, 45.0)
    assert np.isclose(g.sum(), 45.0) and np.all(np.diff(g) > 0)
    p = traffic.picks(traffic.zipf_weights(4, 1.2), 13)
    assert len(p) == 13 and list(np.bincount(p)) == [7, 3, 2, 1]


def test_closed_loop_queues():
    mix = _mix("reasoning-closed")
    s = traffic.schedule(mix, BIG, 45, VOCAB, 17)
    assert len(s.items) == mix["clients"]
    assert {len(q) for q in s.items} == {mix["requests_per_client"]}
    with pytest.raises(ValueError):
        traffic.schedule({**mix, "think_s": 1.0}, BIG, 45, VOCAB, 17)
