"""Each deployment's plain reference equals the engine's fixpoint at a tiny size."""

from __future__ import annotations

import numpy as np

from bench.configs import tc


def _rows(dense) -> np.ndarray:
    return np.argwhere(np.asarray(dense)).astype(np.int64)


def _sorted(rows) -> np.ndarray:
    rows = np.asarray(rows, np.int64).reshape(-1, 2)
    return rows[np.lexsort((rows[:, 1], rows[:, 0]))]


def test_tc_reference_matches_engine():
    from repro.core import Engine

    facts = tc.base_facts({"dataset": {"n": 40, "p": 0.04, "data_seed": 3}})
    got = Engine().run(tc.PROGRAM, facts)["tc"]
    assert np.array_equal(_sorted(got), _sorted(_rows(tc.reference(facts, 40))))
    assert len(got) > len(facts["arc"])


def test_base_facts_are_the_published_generators():
    from repro.data.graphs import gnp_graph

    g = tc.base_facts({"dataset": {"n": 300, "p": 0.01, "data_seed": 0}})
    assert np.array_equal(g["arc"], gnp_graph(300, 0.01, 0))
