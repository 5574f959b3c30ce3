"""Smoke-size cells for the CPU tests: a configuration and a mix of
``bench/`` with every size cut so that the harness runs in seconds on the
CPU (float32, Pallas in interpret mode), measured as the committed cell
``qwen3-0.6b.chat-shared`` is."""
import copy

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
#: (configuration, mix) of each smoke cell: the committed chat cell, and
#: the pool spread over four devices under the closed-loop mix (its files
#: are in bench/; its cell is not in BENCHMARK.json yet)
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
