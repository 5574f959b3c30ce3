"""The architecture interface (``bench/work.py`` ``arch``): one module per
model type, chosen by the configuration file's ``model_type``.  A second
module, added as a file only (``toy_hybrid.py``: Mamba-2 and attention
layers, no query/key norm, an untied head), runs a smoke cell end to end
with the harness, the reference, the weights and the counts as they are."""
import copy
import time

import pytest

from bench import harness, work
from bench.tests.smoke import TOY_CONFIG, smoke_cell

SEED = 2 ** 32 + 2024


def test_a_module_added_as_a_file_runs_a_cell_to_correct(toy_archs):
    c = copy.deepcopy(TOY_CONFIG)
    assert work.arch(c).__file__ == str(toy_archs / "toy_hybrid.py")
    assert work.paged_layers(c) == 2 < c["num_hidden_layers"]
    chat = smoke_cell("chat")
    cell = harness.Cell("toy-hybrid.chat-shared", 1, c, chat.mix,
                        chat.end_to_end, chat.per_layer)
    r = harness.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                         detail=True)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["detail"]["checked_tokens"] > 0
    assert r["metrics"]["itl_p95_s"]["value"] > 0


def test_unknown_model_type_names_the_known_modules():
    with pytest.raises(KeyError, match=r"'granitemoehybrid'.*known: "
                                       r"\['qwen3'\]"):
        work.arch({"model_type": "granitemoehybrid"})


def test_a_module_missing_part_of_the_interface_is_refused(tmp_path,
                                                           monkeypatch):
    (tmp_path / "half.py").write_text("def shapes(c):\n    return {}\n")
    monkeypatch.setattr(work, "ARCHS", tmp_path)
    with pytest.raises(AttributeError, match="model_config"):
        work.arch({"model_type": "half"})
