"""Work the algorithm needs: FLOPs and HBM bytes, from a configuration's
shapes (its ``bench/configs/<name>.json``, Hugging Face key names) and the
context lengths actually served.

Counts are of what decoding needs, not of what the program walks or
copies: attention reads the K/V of the positions a sequence holds, not of
the pages its block table could hold, and a prefill dispatch works for the
one sequence it prefills, not for the whole decode batch it runs.  So a
change that stops walking unmapped pages, or stops copying the pool, moves
the measured share and leaves these counts as they are.

Also the table of peaks (``bench/peaks.json``), keyed by ``device_kind``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
#: bytes per element of the weights and the KV cache (bf16)
ELEM_BYTES = 2


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """Published peaks of one chip of ``device_kind``.  A kind not in the
    table is an error, never a default."""
    table = table or json.loads(PEAKS_FILE.read_text())
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['kinds'])}") from None


def dims(c: dict) -> dict:
    """The sizes the counts use."""
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "H": h,
            "Hkv": hkv, "hd": hd, "ff": c["intermediate_size"],
            "V": c["vocab_size"], "tied": bool(c["tie_word_embeddings"]),
            "qk_norm": c.get("model_type") == "qwen3"}


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's matrix multiplications."""
    k = dims(c)
    attn = k["d"] * (k["H"] + 2 * k["Hkv"]) * k["hd"] + k["H"] * k["hd"] * k["d"]
    return attn + 3 * k["d"] * k["ff"]


def param_count(c: dict) -> int:
    """Every parameter: embedding (once when tied), layers, norms."""
    k = dims(c)
    per_layer = layer_matmul_params(c) + 2 * k["d"]
    if k["qk_norm"]:
        per_layer += 2 * k["hd"]
    emb = k["V"] * k["d"] * (1 if k["tied"] else 2)
    return emb + k["L"] * per_layer + k["d"]


def weight_bytes(c: dict) -> int:
    """Bytes of the weights one decode step reads: all of them, the tied
    table once (the unembedding reads it whole)."""
    return param_count(c) * ELEM_BYTES


def kv_row_bytes(c: dict) -> int:
    """K and V of one position in every layer."""
    k = dims(c)
    return k["L"] * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES


def token_flops(c: dict, ctx: int) -> int:
    """FLOPs to decode one token whose context, itself included, is
    ``ctx`` positions: the matmuls, attention over the context, the
    unembedding."""
    k = dims(c)
    attn = 4 * k["H"] * k["hd"] * ctx
    return 2 * k["L"] * layer_matmul_params(c) + k["L"] * attn \
        + 2 * k["d"] * k["V"]


def step_flops(c: dict, ctxs) -> int:
    """FLOPs of one decode dispatch iteration that decodes one token for
    each context length in ``ctxs``."""
    return sum(token_flops(c, int(n)) for n in ctxs)


def step_bytes(c: dict, ctxs) -> int:
    """Necessary HBM bytes of one decode iteration over ``ctxs``: the
    weights once, the K/V of each context read, one new K/V row written
    per sequence."""
    ctxs = [int(n) for n in ctxs]
    if not ctxs:
        return 0
    return weight_bytes(c) + kv_row_bytes(c) * (sum(ctxs) + len(ctxs))


def gather_attend_work(c: dict, ctxs) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_gather_attend`` over the
    sequences of ``ctxs``: QK and PV over each context, the K/V of each
    context read, the queries read and the f32 partials (acc, m, l)
    written."""
    k = dims(c)
    flops = sum(4 * k["H"] * k["hd"] * int(n) for n in ctxs)
    kv = sum(2 * k["Hkv"] * k["hd"] * int(n) for n in ctxs) * ELEM_BYTES
    io = len(ctxs) * k["H"] * (k["hd"] * ELEM_BYTES + (k["hd"] + 2) * 4)
    return flops, kv + io


def kv_write_work(c: dict, n_seqs: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_kv_write``: one K row and one
    V row per sequence written."""
    k = dims(c)
    return 0, n_seqs * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
