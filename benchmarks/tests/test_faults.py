"""The rest of a run with the timed path broken underneath: the harness
(run.drive) over the reference put in the program's place
(lib/standin.py), once sound and once with each fault a cell can have.
``correct`` has to come out false for every fault, and true without.

  stale_state    a step that returns its state unchanged: a batch is
                 solved against the occupancy it started with (this is
                 also the control, control.py)
  drop_half      half of each batch left out, never decided
  alter_answers  answers altered where they are produced: a batch's
                 bindings all rewritten to its first node

The exchange between chips does not exist in a one-chip cell.
"""

import json

import pytest

from benchmarks import control
from benchmarks.lib import files

CELLS = files.names("workloads")


def holds(row):
    """A compared number against its printed limit ("<= 0", ">= 1")."""
    op, limit = row["limit"].split()
    return row["value"] <= float(limit) if op == "<=" else row["value"] >= float(limit)


def run_control(capsys, cell, fault):
    code = control.main(
        ["--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--fault", fault, "--rehearse-size"]
    )
    assert code == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    return line, out.err


@pytest.mark.parametrize("cell", CELLS)
def test_reference_in_the_programs_place_is_correct(capsys, cell):
    line, err = run_control(capsys, cell, "none")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # each number compared stands beside its limit, last on stderr
    last = err.strip().splitlines()[-len(line["compared"]):]
    assert all(row.startswith("compared ") and "limit" in row for row in last)


@pytest.mark.parametrize("fault", ["stale_state", "drop_half", "alter_answers"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_comes_out_not_correct(capsys, cell, fault):
    line, _ = run_control(capsys, cell, fault)
    assert line["correct"] is False
    broken = {k for k, v in line["compared"].items() if not holds(v)}
    if fault == "drop_half":
        assert broken == {"pods_not_bound"}
    else:
        assert "infeasible_at_commit" in broken
        assert line["failed"] > 0
