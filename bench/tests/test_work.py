"""FLOP and byte counts (``bench/work.py`` through the architecture
modules of ``bench/archs``), the weights' spec, the reference's logits and
the table of peaks."""
import copy
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import harness, reference, work
from bench import weights as W
from bench.tests.smoke import SMOKE_MODEL, TOY_CONFIG, load_toy_arch

CONFIGS = ["qwen3-0.6b", "qwen3-0.6b-pool4"]

#: granite-3-2b at its published widths: a dense sibling of the benchmarked
#: model (no query/key norm), counted by the Qwen3 module's code against
#: the program's registry entry; a count check only, not a cell
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
    if name == "toy_hybrid":
        return copy.deepcopy(TOY_CONFIG)
    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


def _arch(name):
    """The architecture module that counts configuration ``name``."""
    if name == "granite-3-2b":
        return work.arch({"model_type": "qwen3"})
    if name == "toy_hybrid":
        return load_toy_arch()
    return work.arch(_config(name))


@pytest.mark.parametrize("name", CONFIGS + ["granite-3-2b", "toy_hybrid"])
def test_param_count_matches_the_program(name):
    """The count ties to the program's ``Model.init`` at the same shapes
    (by ``jax.eval_shape``: nothing is allocated), less the zero rows the
    program pads its vocabulary (and an untied head) with."""
    from repro.models.model import Model
    c, a = _config(name), _arch(name)
    cfg = a.model_config(c)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    tables = 1 if cfg.tie_embeddings else 2
    pad = (cfg.vocab_padded - cfg.vocab_size) * cfg.d_model * tables
    assert a.param_count(c) == n - pad


@pytest.mark.parametrize("name", CONFIGS + ["granite-3-2b", "toy_hybrid"])
def test_weights_fit_the_program_tree(name):
    """The benchmark's weights, in the program's tree, have the shapes and
    dtypes of ``Model.init``'s (by ``jax.eval_shape``)."""
    from repro.models.model import Model
    c, a = _config(name), _arch(name)
    cfg = a.model_config(c)
    dtype = jax.numpy.dtype(c["torch_dtype"])
    want = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    w = {n: jax.ShapeDtypeStruct(s, dtype)
         for n, (s, _, _) in a.shapes(c).items()}
    if name in CONFIGS:
        assert jax.eval_shape(lambda: W.make(c, 2 ** 33 + 5, dtype)) == w
    got = jax.eval_shape(lambda w: a.program_params(w, cfg), w)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), got) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), want)
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(w)) == \
        a.param_count(c)


def test_qwen3_counts():
    c = _config("qwen3-0.6b")
    # 28 x (1024 x (16 + 16) x 128 + 2048 x 1024 + 3 x 1024 x 3072)
    assert _arch("qwen3-0.6b").layer_matmul_params(c) == 15_728_640
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


#: the weights' spec of both committed configurations as the harness made
#: it before the counts moved into ``bench/archs/qwen3.py``: the same
#: tuple gives the same bits from the same seed
QWEN3_SPEC = (
    ("attn_norm", (28, 1024), "ones", None),
    ("embed", (151936, 1024), "normal", 0.02),
    ("final_norm", (1024,), "ones", None),
    ("k_norm", (28, 128), "ones", None),
    ("mlp_norm", (28, 1024), "ones", None),
    ("q_norm", (28, 128), "ones", None),
    ("w_down", (28, 3072, 1024), "normal", 0.02),
    ("w_gate", (28, 1024, 3072), "normal", 0.02),
    ("w_up", (28, 1024, 3072), "normal", 0.02),
    ("wk", (28, 1024, 1024), "normal", 0.02),
    ("wo", (28, 2048, 1024), "normal", 0.02),
    ("wq", (28, 1024, 2048), "normal", 0.02),
    ("wv", (28, 1024, 1024), "normal", 0.02))
#: every count at fixed context lists, as counted before the move
CTXS = ([1], [7, 300, 2048], [4096] * 32)
QWEN3_COUNTS = {
    "paged_layers": 28, "param_count": 596_049_920,
    "weight_bytes": 1_192_099_840, "kv_row_bytes": 114_688,
    "token_flops": [1_192_198_144, 1_193_574_400, 2_131_492_864],
    "step_flops": [1_192_198_144, 4_116_086_784, 68_207_771_648],
    "step_bytes": [1_192_329_216, 1_462_534_144, 16_228_155_392],
    "gather_attend_work": [(8192, 16_512), (19_292_160, 9_683_328),
                           (1_073_741_824, 537_268_224)],
    "kv_write_work": [(0, 4096), (0, 12_288), (0, 131_072)]}


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_spec_is_pinned(name):
    assert W.spec(_config(name)) == QWEN3_SPEC


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_pinned(name):
    c = _config(name)
    assert work.paged_layers(c) == c["num_hidden_layers"]
    got = {"paged_layers": work.paged_layers(c),
           "param_count": work.param_count(c),
           "weight_bytes": work.weight_bytes(c),
           "kv_row_bytes": work.kv_row_bytes(c),
           "token_flops": [work.token_flops(c, x[0]) for x in CTXS],
           "step_flops": [work.step_flops(c, x) for x in CTXS],
           "step_bytes": [work.step_bytes(c, x) for x in CTXS],
           "gather_attend_work": [work.gather_attend_work(c, x)
                                  for x in CTXS],
           "kv_write_work": [work.kv_write_work(c, len(x)) for x in CTXS]}
    assert got == QWEN3_COUNTS


#: the smoke configuration's reference logits (float32 and the float8
#: control) at a fixed seed and prompt, as computed before the move: 37
#: positions x 512 logits, a few written out, all by digest
SMOKE_SEED = 2 ** 33 + 17
SMOKE_TOKENS = [343, 412, 11, 413, 240, 263, 322, 146, 501, 27, 142, 196,
                292, 209, 67, 23, 0, 24, 76, 511, 97, 334, 384, 120, 144,
                222, 134, 498, 90, 459, 408, 432, 59, 200, 321, 252, 340]
SMOKE_LOGITS = [-1.5515697002410889, 0.4367526173591614, 0.629960298538208,
                -0.20674368739128113, -0.6996575593948364,
                -0.07744079828262329, 0.20272402465343475,
                0.39645323157310486, -0.23364874720573425]
SMOKE_SHA256 = {
    "f32": "f535f23c1552764c521a19f7a54bb4bb6a450ae9d7210394edc42b095144cd69",
    "fp8": "d03e9e7eabb7fc4ded00fc177737f46ef998436ab245a3d55e94a77fe14c17c2"}


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_smoke_reference_logits_are_pinned(mode):
    c = _config("qwen3-0.6b")
    c.update(SMOKE_MODEL)
    w = reference.reference_weights(c, SMOKE_SEED)
    got = reference.logits(c, w, np.array(SMOKE_TOKENS, np.int32), mode)
    assert got.shape == (37, 512) and got.dtype == np.float32
    if mode == "f32":
        np.testing.assert_array_equal(got[[0, 17, 36], :3].ravel(),
                                      np.float32(SMOKE_LOGITS))
    assert hashlib.sha256(got.tobytes()).hexdigest() == SMOKE_SHA256[mode]


class _KernelTrace:
    """What the kernel readers read of a trace: each kernel 1 ms."""
    def kernel_s(self, pattern):
        return 1e-3


@pytest.mark.parametrize("metric", ["gather_attend_roofline",
                                    "kv_write_roofline"])
def test_kernel_readers_count_paged_layers_only(metric, toy_archs):
    """The toy hybrid holds paged K/V in 2 of its 4 layers: a kernel's
    work is its one-layer work times 2."""
    c = copy.deepcopy(TOY_CONFIG)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    spans = [harness.Span("step", 0.0, 1.0, ctxs=[[10, 20], [11, 21]],
                          traced=True)]
    view = harness.RunView(c, spans, 1.0, _KernelTrace(), peak, 1, 0)
    if metric == "gather_attend_roofline":
        works = [work.gather_attend_work(c, x) for x in spans[0].ctxs]
    else:
        works = [work.kv_write_work(c, len(x)) for x in spans[0].ctxs]
    flops, nbytes = (sum(w[i] for w in works) for i in (0, 1))
    assert work.paged_layers(c) == 2
    want = 100.0 * work.roofline_s(2 * flops, 2 * nbytes, peak) / 1e-3
    assert harness.load_metric(metric).read(view) == pytest.approx(want)
