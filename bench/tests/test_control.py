"""Each cell's control (``bench/control.py``) at a size a test run holds:
judged by the cell driver's own check, it has to fail on every seed."""
import pytest

from bench import control

from conftest import small_cell


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3])
@pytest.mark.parametrize("cell", ["grouper-reads.build",
                                  "grouper-reads-ooc.build"])
def test_build_control_fails(cell, seed, tmp_path):
    checks, attempted, failed = control.build_control(
        small_cell(cell), seed, 2, str(tmp_path))
    assert attempted == 2 and failed > 0
    assert checks["sa_defects"][0] > checks["sa_defects"][1]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3])
def test_lookup_control_fails(seed, tmp_path):
    checks, attempted, failed = control.lookup_control(
        small_cell("grouper-seed-index.lookup"), seed, 20, str(tmp_path))
    assert attempted > 0 and failed > 0
    for name in ("count_defects", "locate_defects"):
        assert checks[name][0] > checks[name][1]
