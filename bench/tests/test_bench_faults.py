"""``correct`` comes out false with the timed path broken underneath.

Each fault of ``bench/faults.py`` is planted in a whole tiny run on the CPU
(the chip check skipped): the control (``stale_read``, answers from the
epoch before the one the query pinned), a transaction that leaves the state
unchanged, one that drops half its rows, and altered answers.  The cell has
one chip, so there is no exchange between chips to leave out.
"""

from __future__ import annotations

import pytest

from bench.faults import FAULTS
from bench.tests._tiny import run_tiny

CAUGHT_BY = {
    "stale_read": {"answers_wrong"},
    "txn_unchanged": {"epochs_off", "fixpoint_off"},
    "txn_half": {"fixpoint_off"},
    "answer_altered": {"answers_wrong"},
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_run(fault):
    out, _run = run_tiny(fault=fault)
    assert out["correct"] is False
    over = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert CAUGHT_BY[fault] <= over, over
