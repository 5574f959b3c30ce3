"""FLOP and byte counts (``bench/work.py``) and the table of peaks."""
import copy
import json

import jax
import numpy as np
import pytest

from bench import harness, work
from bench import weights as W

CONFIGS = ["qwen3-0.6b", "qwen3-0.6b-pool4"]

#: granite-3-2b at its published widths: the next configuration, whose
#: registry entry the harness would run with the same code
GRANITE = {"arch": "granite-3-2b", "model_type": "granite",
           "num_hidden_layers": 40, "hidden_size": 2048,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 64, "intermediate_size": 8192, "vocab_size": 49155,
           "initializer_range": 0.02,
           "tie_word_embeddings": True, "rms_norm_eps": 1e-6,
           "rope_theta": 10000.0, "torch_dtype": "bfloat16",
           "serving": {"kv_layout": "pooled", "paged_kernel": "fused",
                       "page_slots": 256}}


def _config(name):
    if name == "granite-3-2b":
        return copy.deepcopy(GRANITE)
    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", CONFIGS + ["granite-3-2b"])
def test_param_count_matches_the_program(name):
    """The count ties to the program's ``Model.init`` at the same shapes
    (by ``jax.eval_shape``: nothing is allocated), less the zero rows the
    program pads its vocabulary with."""
    from repro.models.model import Model
    c = _config(name)
    cfg = harness.model_config(c)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    pad = (cfg.vocab_padded - cfg.vocab_size) * cfg.d_model
    assert work.param_count(c) == n - pad


@pytest.mark.parametrize("name", CONFIGS + ["granite-3-2b"])
def test_weights_fit_the_program_tree(name):
    """The benchmark's weights, in the program's tree, have the shapes and
    dtypes of ``Model.init``'s (by ``jax.eval_shape``)."""
    from repro.models.model import Model
    c = _config(name)
    cfg = harness.model_config(c)
    want = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    w = jax.eval_shape(lambda: W.make(c, 2 ** 33 + 5))
    got = jax.eval_shape(lambda w: harness.program_params(w, cfg), w)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), got) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), want)
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(w)) == \
        work.param_count(c)


def test_qwen3_counts():
    c = _config("qwen3-0.6b")
    # 28 x (1024 x (16 + 16) x 128 + 2048 x 1024 + 3 x 1024 x 3072)
    assert work.layer_matmul_params(c) == 15_728_640
    assert work.weight_bytes(c) == 2 * work.param_count(c)
    assert work.kv_row_bytes(c) == 114_688          # 28 x 2 x 8 x 128 x 2
    assert work.token_flops(c, 1) == 2 * 28 * 15_728_640 \
        + 28 * 4 * 16 * 128 + 2 * 1024 * 151_936
    # one iteration: weights once, each context read, a row written each
    assert work.step_bytes(c, [10, 20]) == work.weight_bytes(c) \
        + 114_688 * 32
    assert work.step_bytes(c, []) == 0
    assert work.step_flops(c, [10, 20]) == work.token_flops(c, 10) \
        + work.token_flops(c, 20)


def test_kernel_work_counts_actual_lengths():
    c = _config("qwen3-0.6b")
    f, b = work.gather_attend_work(c, [100, 300])
    assert f == 4 * 16 * 128 * 400
    assert b == 2 * 8 * 128 * 400 * 2 + 2 * 16 * (128 * 2 + 130 * 4)
    assert work.kv_write_work(c, 3) == (0, 3 * 2 * 8 * 128 * 2)


def test_roofline_takes_the_larger_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000, 10, p) == 10.0
    assert work.roofline_s(100, 100, p) == 10.0


def test_peaks_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")
