"""Smoke-size cells for the CPU tests: a configuration and a mix of
``bench/`` with every size cut so that the harness runs in seconds on the
CPU (float32, Pallas in interpret mode), measured as the committed cell
``qwen3-0.6b.chat-shared`` is."""
import copy
import importlib.util
from pathlib import Path

from bench import harness

#: widths of the smoke model (hd 32, 2 query heads per KV head).  The
#: initializer's std is scaled by sqrt(1024 / 128) so that each layer
#: adds to the residual stream about what a layer of the full model adds
#: at 0.02: at 0.02 here the embedding dominates, every token predicts
#: itself, and a fault that loses the context would go unseen.
SMOKE_MODEL = {"num_hidden_layers": 2, "hidden_size": 128,
               "initializer_range": 0.06,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "intermediate_size": 256,
               "vocab_size": 512, "torch_dtype": "float32"}
#: float32 program against the float32 reference: rounding only
SMOKE_LIMIT = 1e-3
#: (configuration, mix, devices) of each smoke cell: the chat cell, and the
#: pool spread over four devices under the closed-loop mix
CELLS = {"chat": ("qwen3-0.6b", "chat-shared", 1),
         "pool4": ("qwen3-0.6b-pool4", "reasoning-closed", 4)}


def smoke_cell(which: str, kernel: str = "fused") -> harness.Cell:
    config, traffic, chips = CELLS[which]
    base = harness.load("qwen3-0.6b.chat-shared")
    c = copy.deepcopy(harness.read_config(config))
    c.update(SMOKE_MODEL)
    c["serving"].update(page_slots=16, slots=4, max_len=128,
                        paged_kernel=kernel, max_fused_steps=4)
    if c["serving"]["retain_frames"]:
        c["serving"]["retain_frames"] = 4
    c["check"] = {"max_logit_gap": SMOKE_LIMIT}
    mix = copy.deepcopy(harness.read_mix(traffic))
    if mix["loop"] == "open":
        mix["rate_per_s"] = 3.0
        mix["lead_s"] = 1.0
        mix["prefix"]["tokens"] = 32
        mix["prompt"].update(median=8, min=4, max=16)
        mix["output"].update(median=8, min=4, max=16)
        mix["check_tokens"] = 1024
    else:
        mix.update(clients=4, warmup_s=4.0, requests_per_client=64)
        mix["prompt"].update(median=8, min=4, max=16)
        mix["output"].update(median=12, min=8, max=24)
        mix["check_tokens"] = 24
    return harness.Cell(f"{config}.{traffic}", chips, c, mix,
                        base.end_to_end, base.per_layer)


#: the test-only architecture module (``toy_hybrid.py`` beside this file)
#: and a smoke configuration of it: 4 layers, Mamba-2 then attention in
#: each period of 2, so 2 of the 4 layers hold paged K/V
TOY_ARCH = Path(__file__).with_name("toy_hybrid.py")
TOY_CONFIG = {
    "arch": "jamba-v0.1-52b", "model_type": "toy_hybrid",
    "num_hidden_layers": 4, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 512, "initializer_range": 0.06,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "torch_dtype": "float32",
    "attn_layer_period": 2, "attn_layer_offset": 1, "mamba_d_state": 8,
    "mamba_d_head": 32, "mamba_expand": 2, "mamba_d_conv": 4,
    "mamba_n_groups": 1,
    "serving": {"kv_layout": "pooled", "paged_kernel": "fused",
                "page_slots": 16, "slots": 4, "max_len": 128, "kv_shards": 1,
                "retain_frames": 0, "max_fused_steps": 4},
    "check": {"max_logit_gap": SMOKE_LIMIT}}


def load_toy_arch():
    """``toy_hybrid.py`` as a module, loaded by path as ``bench.work``
    loads the modules of ``bench/archs``."""
    spec = importlib.util.spec_from_file_location("bench_arch_toy_hybrid",
                                                  TOY_ARCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
