"""Whole runs of the tiny build cell on the CPU, past the look for a
chip: a sound program comes out correct, and the control does not."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))
import perfbench_tiny  # noqa: E402
from perfbench_tiny import run_cell  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return perfbench_tiny.make_root(tmp_path_factory.mktemp("bench"))


CONTROL = perfbench_tiny.load_file(
    HERE.parent / "tools" / "control.py", "perfbench_control_tool")


def test_build_cell_is_correct(root, capsys):
    line = run_cell(root, capsys, "tiny-kron.build")
    assert line["correct"] is True and line["attempted"] >= 1
    assert set(line["metrics"]) == {"build_s", "setup_s"}
    traced = run_cell(root, capsys, "tiny-kron.build", trace=1)
    assert set(traced["metrics"]) == {"build.peel_s", "build.label_s"}


def test_control_fails_and_program_passes(root, capsys):
    capsys.readouterr()
    CONTROL.main(["--workload", "tiny-kron.build", "--seeds", "5,6",
                  "--rounds", "1"], require_chip=False, root=root)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith('{"path"')]
    program = [r for r in rows if r["path"] == "program"]
    capped = [r for r in rows if r["path"] != "program"]
    assert len(program) == len(capped) == 2
    assert all(r["wrong_answers"] == 0 for r in program)
    assert all(r["wrong_answers"] > 0 for r in capped)
