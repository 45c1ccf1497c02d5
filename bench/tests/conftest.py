"""CPU tests of the benchmark's own arithmetic and of its correctness check.

They import the benchmark as the ``bench`` package from the checkout root
and the program from ``src``; nothing here touches an accelerator.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load(name: str) -> dict:
    return json.loads((DATA / name).read_text())


@pytest.fixture
def data():
    return load
