"""The benchmark's CPU tests import the program from the checkout's src/."""
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))


@pytest.fixture
def toy_archs(tmp_path, monkeypatch):
    """The architecture loader's directory with the test-only module
    ``toy_hybrid.py`` placed beside the committed ones."""
    from bench import work
    from bench.tests.smoke import TOY_ARCH
    archs = tmp_path / "archs"
    archs.mkdir()
    for f in work.ARCHS.glob("*.py"):
        shutil.copy(f, archs)
    shutil.copy(TOY_ARCH, archs)
    monkeypatch.setattr(work, "ARCHS", archs)
    return archs
