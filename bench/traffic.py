"""Traffic generation from a mix file (``bench/traffic/<mix>.json``).

One general generator reads every mix.  A mix is open loop (Poisson
arrivals at a fixed rate, in seconds) or closed loop (a fixed number of
clients, each sending its next request when its last reply completes).
Lengths are lognormal with a median and a sigma, rounded and clipped to a
range.  Shared prefixes (system prompts) are picked by Zipf popularity.

Every seed offers the same work, in an order of its own.  The sizes of a
period (the window, or the open loop's lead before it) are the
distribution's quantiles at the midpoints of ``n`` equal steps: the
arrival gaps the exponential's, the lengths the clipped lognormal's, the
prefix picks each prefix's Zipf share of ``n`` (largest remainders).  The
seed draws the order of each of them, and the token ids.  So the arrivals,
the pairing of prompts with outputs and prefixes, and which requests fall
early or late in the window change with the seed, while the amount of work
in a window does not: with some tens of requests in a window, a draw of the
sizes themselves would change the load from run to run by more than a
change to the program does.  The Poisson spacing and the Zipf weights are
the arithmetic of ``repro.serve.tracegen`` (there in decode steps, here in
seconds).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    """One request to send: when (seconds after the window opens, open loop
    only; negative in the lead before it), its prompt, its output budget,
    and which shared prefix it starts with (-1: none)."""
    due_s: float
    prompt: np.ndarray
    max_new: int
    prefix: int = -1


@dataclasses.dataclass
class Schedule:
    """``items`` to send (open loop: sorted by due time, the lead's first;
    closed loop: ``clients`` queues of requests each); ``warm`` is served
    before any of them (one request per shared prefix)."""
    loop: str
    items: list
    warm: list


def zipf_weights(n: int, alpha: float) -> np.ndarray:
    """Normalized Zipf popularity over ranks 1..n: P(rank k) ~ k^-alpha."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(alpha)
    return w / w.sum()


def _mid(n: int) -> np.ndarray:
    """The midpoints of ``n`` equal steps of (0, 1)."""
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths of a clipped lognormal ``spec``: its quantiles at
    ``_mid(n)``, rounded, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _mid(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(n: int, total: float) -> np.ndarray:
    """``n`` exponential gaps (the quantiles at ``_mid(n)``) scaled to sum
    to ``total``, ascending."""
    g = -np.log1p(-_mid(n))
    return g * (total / g.sum())


def picks(weights: np.ndarray, n: int) -> np.ndarray:
    """``n`` picks with each index's share of ``n`` by ``weights``, whole
    counts by largest remainder, ascending."""
    want = weights * n
    counts = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(len(weights)), counts)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, *stream])


def _period(mix: dict, n: int, span: float, start: float, prefixes,
            rng: np.random.Generator, vocab: int) -> list:
    """``n`` requests due in [start, start + span): each size set of the
    period in an order drawn from ``rng``."""
    pre = mix.get("prefix")
    due = start + np.cumsum(rng.permutation(gaps(n + 1, span)))[:n]
    pick = (rng.permutation(picks(
        zipf_weights(pre["count"], pre["zipf_alpha"]), n))
        if pre else np.full(n, -1))
    tails = rng.permutation(lengths(mix["prompt"], n))
    outs = rng.permutation(lengths(mix["output"], n))
    items = []
    for i in range(n):
        tail = rng.integers(0, vocab, int(tails[i])).astype(np.int32)
        p = int(pick[i])
        prompt = tail if p < 0 else np.concatenate([prefixes[p], tail])
        items.append(Item(float(due[i]), prompt, int(outs[i]), p))
    return items


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              warm_new: int) -> Schedule:
    """Poisson arrivals at ``rate_per_s``: ``lead_s`` seconds of them before
    the window (served in set-up, so that the window opens on a loaded
    engine), then ``seconds`` of them in the window.  Each period holds
    rate x its length requests."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    rate, lead = mix["rate_per_s"], mix.get("lead_s", 0)
    pre = mix.get("prefix")
    rng = _rng(seed)
    prefixes = (rng.integers(0, vocab, (pre["count"], pre["tokens"]))
                .astype(np.int32) if pre else None)
    items = []
    if lead:
        items += _period(mix, max(1, int(round(rate * lead))), lead, -lead,
                         prefixes, _rng(seed, 1), vocab)
    items += _period(mix, max(1, int(round(rate * seconds))), seconds, 0.0,
                     prefixes, _rng(seed, 2), vocab)
    # the warm-up serves each shared prefix alone: its pages are whole, and
    # the retention pool keeps every page of a completed prompt
    warm = [Item(0.0, prefixes[p], warm_new, p)
            for p in range(pre["count"])] if pre and pre.get("warm") else []
    return Schedule("open", items, warm)


def closed_loop(mix: dict, seed: int, vocab: int) -> Schedule:
    """``clients`` queues of ``requests_per_client`` requests each, sent
    back to back (``think_s`` must be 0: no think time)."""
    if mix.get("think_s", 0) != 0:
        raise ValueError("closed loop with think time is not supported")
    c, k = mix["clients"], mix["requests_per_client"]
    rng = _rng(seed)
    plens = rng.permutation(lengths(mix["prompt"], c * k))
    outs = rng.permutation(lengths(mix["output"], c * k))
    queues = []
    for j in range(c):
        q = []
        for i in range(j * k, (j + 1) * k):
            prompt = rng.integers(0, vocab, int(plens[i])).astype(np.int32)
            q.append(Item(0.0, prompt, int(outs[i])))
        queues.append(q)
    return Schedule("closed", queues, [])


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             warm_new: int) -> Schedule:
    """The schedule a mix gives for ``seed`` and a window of ``seconds``."""
    if mix["loop"] == "open":
        return open_loop(mix, seed, seconds, vocab, warm_new)
    if mix["loop"] == "closed":
        return closed_loop(mix, seed, vocab)
    raise ValueError(f"unknown loop {mix['loop']!r}")
