"""Work the algorithm needs: FLOPs and HBM bytes, from a configuration's
shapes (its ``bench/configs/<name>.json``, Hugging Face key names) and the
context lengths actually served.

Counts are of what decoding needs, not of what the program walks or
copies: attention reads the K/V of the positions a sequence holds, not of
the pages its block table could hold, and a prefill dispatch works for the
one sequence it prefills, not for the whole decode batch it runs.  So a
change that stops walking unmapped pages, or stops copying the pool, moves
the measured share and leaves these counts as they are.

Everything that depends on the architecture lives in one module per model
type, ``bench/archs/<model_type>.py``, which ``arch(c)`` loads by the
configuration file's own ``model_type`` key.  The counts here call through
it.  A module fills in ``INTERFACE``:

- ``shapes(c)``: the weights, name -> (shape, init, std), which
  ``bench/weights.py`` makes;
- ``model_config(c)``, ``program_params(w, cfg)``: the program's
  ``ModelConfig`` and the weights in its parameter tree;
- ``forward_key(c)``, ``forward(key, w, tokens, mode)``: the plain
  reference's logits of one sequence, jitted for the static ``key``, which
  ``bench/reference.py`` compares with;
- ``paged_layers(c)``: the layers that hold paged K/V, which each kernel
  reader multiplies its one-layer work by;
- the counts below, each with the same arguments.

Also the table of peaks (``bench/peaks.json``), keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
#: one module per model type, ``<model_type>.py``
ARCHS = Path(__file__).resolve().parent / "archs"
#: what every module of ``ARCHS`` defines
INTERFACE = ("shapes", "model_config", "program_params", "forward_key",
             "forward", "paged_layers", "param_count", "weight_bytes",
             "kv_row_bytes", "token_flops", "step_flops", "step_bytes",
             "gather_attend_work", "kv_write_work")
#: bytes per element of the weights and the KV cache (bf16)
ELEM_BYTES = 2

_loaded: dict = {}


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """Published peaks of one chip of ``device_kind``.  A kind not in the
    table is an error, never a default."""
    table = table or json.loads(PEAKS_FILE.read_text())
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['kinds'])}") from None


def arch(c: dict):
    """The module of ``c``'s ``model_type``, loaded once from ``ARCHS``.
    A type with no module is an error that names the known ones, never a
    fallback."""
    mt = c.get("model_type")
    path = ARCHS / f"{mt}.py"
    if path not in _loaded:
        if not path.is_file():
            known = sorted(f.stem for f in ARCHS.glob("*.py"))
            raise KeyError(f"no architecture module for model_type {mt!r} "
                           f"in {ARCHS}; known: {known}")
        spec = importlib.util.spec_from_file_location(f"bench_arch_{mt}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [n for n in INTERFACE if not hasattr(mod, n)]
        if missing:
            raise AttributeError(f"{path} does not define {missing}")
        _loaded[path] = mod
    return _loaded[path]


def paged_layers(c: dict) -> int:
    """Layers that hold paged K/V."""
    return arch(c).paged_layers(c)


def param_count(c: dict) -> int:
    """Every parameter of the model."""
    return arch(c).param_count(c)


def weight_bytes(c: dict) -> int:
    """Bytes of the weights one decode step reads."""
    return arch(c).weight_bytes(c)


def kv_row_bytes(c: dict) -> int:
    """K and V of one position in every layer that holds them."""
    return arch(c).kv_row_bytes(c)


def token_flops(c: dict, ctx: int) -> int:
    """FLOPs to decode one token whose context, itself included, is
    ``ctx`` positions."""
    return arch(c).token_flops(c, ctx)


def step_flops(c: dict, ctxs) -> int:
    """FLOPs of one decode dispatch iteration that decodes one token for
    each context length in ``ctxs``."""
    return arch(c).step_flops(c, ctxs)


def step_bytes(c: dict, ctxs) -> int:
    """Necessary HBM bytes of one decode iteration over ``ctxs``; 0 for
    none."""
    return arch(c).step_bytes(c, ctxs)


def gather_attend_work(c: dict, ctxs) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_gather_attend`` over the
    sequences of ``ctxs``."""
    return arch(c).gather_attend_work(c, ctxs)


def kv_write_work(c: dict, n_seqs: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_kv_write`` for ``n_seqs``
    sequences."""
    return arch(c).kv_write_work(c, n_seqs)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
