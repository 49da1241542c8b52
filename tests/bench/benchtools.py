"""What the benchmark's tests share: the repository on ``sys.path`` and
a root of cells defined by data alone (``data/root``).  Imported by name
from each test file (a second ``conftest`` module would shadow the
suite's own)."""

import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def cell_root(tmp_path):
    """A benchmark root made of the test cells' data files, with the
    repository's metric readers, peaks table, dense reference and work
    counts beside them."""
    root = tmp_path / "root"
    shutil.copytree(DATA / "root", root)
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    for name in ("peaks.json", "reference.py", "work.py"):
        shutil.copy(REPO / "bench" / name, root / "bench" / name)
    return root
