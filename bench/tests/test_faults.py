"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a served cell can have, and the control (the float8
reference in the program's place) fails the limit the program passes.

Each test drives a whole run through ``harness.run_cell`` (the look for a
chip is the command's, and is skipped) at smoke size on the CPU.  The
faults are planted in the program's step functions, which the engine
imports at each call:

- a step that returns its state unchanged: the cache it was given;
- a token altered where it is produced: the sampled ids, plus one;
- the exchange between chips left out: the log-sum-exp merge's psum and
  pmax replaced by the identity (four virtual devices, own process).

Half of a batch left out with the mean over the rest is a fault of a
training step; a served cell has no such mean.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.smoke import SMOKE_LIMIT, smoke_cell

SEED = 2 ** 31 + 99
ROOT = str(harness.ROOT)


def _run(cell, **kw):
    return harness.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                            **kw)


def _unchanged_state(fd):
    step, run = fd.sampled_decode_step, fd.fused_decode_run

    def step_(model, params, toks, cache, lengths, wm):
        sampled, logits, _ = step(model, params, toks, cache, lengths, wm)
        return sampled, logits, cache

    def run_(model, cap, params, toks, cache, *rest):
        buf, n, _, lengths = run(model, cap, params, toks, cache, *rest)
        return buf, n, cache, lengths
    return step_, run_


def _altered_token(fd):
    step, run = fd.sampled_decode_step, fd.fused_decode_run

    def step_(model, *args):
        sampled, logits, cache = step(model, *args)
        return (sampled + 1) % model.cfg.vocab_size, logits, cache

    def run_(model, *args):
        buf, n, cache, lengths = run(model, *args)
        return (buf + 1) % model.cfg.vocab_size, n, cache, lengths
    return step_, run_


@pytest.mark.parametrize("fault", [_unchanged_state, _altered_token])
def test_fault_fails_the_check(monkeypatch, fault):
    from repro.serve import fused_decode as fd
    step_, run_ = fault(fd)
    monkeypatch.setattr(fd, "sampled_decode_step", step_)
    monkeypatch.setattr(fd, "fused_decode_run", run_)
    r = _run(smoke_cell("chat"))
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]


NO_EXCHANGE = """
import jax, json, time
from repro.parallel import paged_attention as pa
from bench import harness
from bench.tests.smoke import smoke_cell

class NoExchange:
    def __getattr__(self, name):
        if name in ("psum", "pmax"):
            return lambda x, axes: x
        return getattr(jax.lax, name)

class Jax:
    lax = NoExchange()
    def __getattr__(self, name):
        return getattr(jax, name)

pa.jax = Jax()
cell = smoke_cell("pool4")
print(json.dumps(harness.run_cell(cell, 2 ** 31 + 99, 3.0, False,
                                  time.perf_counter())))
"""


def test_missing_exchange_between_chips_fails_the_check():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "src")])}
    p = subprocess.run([sys.executable, "-c", NO_EXCHANGE], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]


def test_control_fails_the_limit_the_program_passes():
    """At smoke size (float32) the float8 control, put in the program's
    place in the check, comes out not correct where the program is."""
    r = _run(smoke_cell("chat"), control=True, detail=True)
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    d = r["detail"]
    assert d["checked_tokens"] > 0
    assert d["program_gap"] <= SMOKE_LIMIT == gap["limit"] < gap["value"]
