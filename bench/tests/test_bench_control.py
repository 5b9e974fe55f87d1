"""What the comparison refuses: the lower-precision control in the
program's place, and the faults a run can have, each planted in the
program underneath an otherwise whole run."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import reference
from bench.tests.conftest import run_cell


@pytest.mark.parametrize("cell", ["tiny.sessions", "tiny.search"])
def test_the_bfloat16_control_is_not_correct(tiny_root, cell):
    out = run_cell(tiny_root, "--workload", cell, "--seed", "21", "--seconds", "1.5", "--control", "bf16")
    assert out["correct"] is False
    assert out["checks"]["dist_gap"]["value"] > 10 * out["checks"]["dist_gap"]["limit"]


def test_ranking_in_bfloat16_is_not_correct_even_with_exact_distances(tiny_root):
    out = run_cell(tiny_root, "--workload", "tiny.sessions", "--seed", "22", "--seconds", "1.5",
                   "--control", "bf16-rank")
    assert out["correct"] is False
    assert out["checks"]["out_of_order"]["value"] > 0
    assert out["checks"]["dist_gap"]["value"] <= out["checks"]["dist_gap"]["limit"]


def _page(d, leaves):
    return SimpleNamespace(done=1.0, ids=np.arange(len(d)), dists=np.asarray(d, np.float32),
                           stats=SimpleNamespace(leaves_opened=leaves))


@pytest.mark.parametrize("second, leaves, out", [
    ([0.3, 0.4], 8, False),   # the next candidates
    ([0.2, 0.4], 8, False),   # a tie at the boundary
    ([0.15, 0.4], 8, True),   # one nearer than the first page's last
    ([0.15, 0.4], 9, False),  # nearer, but found in a leaf opened since
])
def test_a_more_page_is_out_of_order_only_from_the_same_leaves(second, leaves, out):
    pages = [_page([0.1, 0.2], 8), _page(second, leaves)]
    assert reference._out_of_order(pages) is out


def _stash_first_page(monkeypatch):
    from repro.core import search

    orig = search.ECPIndex._result

    def result(self, rows, states, k, single, query):
        rs = orig(self, rows, states, k, single, query)
        query.first = (rs.dists.copy(), rs.ids.copy())
        return rs

    monkeypatch.setattr(search.ECPIndex, "_result", result)
    return search


def unchanged_state(monkeypatch):
    """A continuation that does not advance: more() gives the first page."""
    from repro.core.api import ResultSet

    search = _stash_first_page(monkeypatch)
    monkeypatch.setattr(
        search.ECPQuery, "next",
        lambda self, k: ResultSet(dists=self.first[0], ids=self.first[1], query=self),
    )


def altered_answer(monkeypatch):
    """One id of every page replaced where the page is made."""
    from repro.core import search

    orig = search.ECPIndex._result

    def result(self, rows, states, k, single, query):
        rs = orig(self, rows, states, k, single, query)
        rs.ids = rs.ids.copy()
        rs.ids[..., k // 2] = (rs.ids[..., k // 2] + 1) % self.info.n_items
        return rs

    monkeypatch.setattr(search.ECPIndex, "_result", result)


def half_the_page(monkeypatch):
    """Half of every page left out."""
    from repro.core import search

    orig = search.ECPIndex._result

    def result(self, rows, states, k, single, query):
        rs = orig(self, rows, states, k, single, query)
        rs.ids, rs.dists = rs.ids.copy(), rs.dists.copy()
        rs.ids[..., k // 2:] = -1
        rs.dists[..., k // 2:] = np.inf
        return rs

    monkeypatch.setattr(search.ECPIndex, "_result", result)


@pytest.mark.parametrize("fault, cell, fails", [
    (unchanged_state, "tiny.sessions", "bad_ids"),
    (altered_answer, "tiny.sessions", "dist_gap"),
    (altered_answer, "tiny.search", "dist_gap"),
    (half_the_page, "tiny.search", "short"),
])
def test_a_planted_fault_is_not_correct(tiny_root, monkeypatch, fault, cell, fails):
    fault(monkeypatch)
    out = run_cell(tiny_root, "--workload", cell, "--seed", "31", "--seconds", "1", "--trace", "0")
    assert out["correct"] is False
    c = out["checks"][fails]
    assert c["value"] > c["limit"]
