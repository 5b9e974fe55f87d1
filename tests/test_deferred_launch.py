"""Deferred quantized leaf scans (core/search.py ``_quant_scan``).

An increment's (query, leaf) scan units are recorded round by round and
scanned at its end in a few grouped launches, sorted by rows.  The pending
entries the rerank gets and the pages it makes are bit-identical to one
launch per round; a query launches groups of at most 8, a batch never
launches more often than it had rounds; and the kernel wrapper pads any
launch to at least 8 groups, so a one-group warm-up call compiles the
program a search's launches run.
"""
import math
import shutil

import numpy as np
import pytest

from repro.core import ECPBuildConfig, build_index, convert, open_index
from repro.core.search import ECPIndex
from repro.kernels.distance_topk import grouped, ops

N, DIM = 1500, 16


@pytest.fixture(scope="module")
def blobs(tmp_path_factory):
    d = tmp_path_factory.mktemp("deferred")
    data = np.random.default_rng(5).standard_normal((N, DIM)).astype(np.float32)
    build_index(data, str(d / "fs"), ECPBuildConfig(levels=2, metric="l2", cluster_cap=48))
    plain = convert(str(d / "fs"), d / "idx.blob", quant="int8")
    tomb = d / "tomb.blob"
    shutil.copy(plain, tomb)
    ix = open_index(str(tomb))
    assert ix.delete(np.arange(0, N, 7)) > 0  # tombstones in most leaves
    ix.close()
    return {"plain": str(plain), "tomb": str(tomb)}


def _queries(n, seed=11):
    return np.random.default_rng(seed).standard_normal((n, DIM)).astype(np.float32)


class _PerRound:
    """One grouped launch per round, packed and recorded as the round
    engine did before the scans were deferred: the reference."""

    def __init__(self, monkeypatch):
        self.cuts: dict[int, list] = {}
        record = ECPIndex._quant_record

        def recording(idx, *a):
            had_units = record(idx, *a)
            deferred = a[-1]
            self.cuts.setdefault(id(deferred), [0]).append(len(deferred))
            return had_units

        monkeypatch.setattr(ECPIndex, "_quant_record", recording)
        monkeypatch.setattr(ECPIndex, "_quant_scan", lambda idx, *a: self.scan(idx, *a))

    def scan(self, idx, units, rounds, agg):
        cuts = self.cuts.pop(id(units), [0])
        pending = []
        for lo, hi in zip(cuts, cuts[1:]):
            rnd = units[lo:hi]
            if not rnd:
                continue
            n_max = max(u[2].n_rows for u in rnd)
            r_max = max(u[3] for u in rnd)
            kop = min(n_max, -(-(r_max + 16) // 32) * 32)
            codes = np.zeros((len(rnd), n_max, DIM), np.int8)
            for g, u in enumerate(rnd):
                codes[g, : u[2].n_rows] = u[2].codes
            dists, idxs = ops.grouped_distance_topk(
                np.stack([u[0].q for u in rnd]).astype(np.float32), codes,
                np.array([u[2].scale for u in rnd], np.float32),
                np.array([u[2].offset for u in rnd], np.float32),
                np.array([u[2].n_rows for u in rnd], np.int32),
                kop, idx.info.metric, idx._qformat,
            )
            agg.kernel_launches += 1
            for g, (qs, key, qn, R, _, dead_rows) in enumerate(rnd):
                valid = idxs[g] >= 0
                pending.append((
                    qs, key, qn, R, dists[g][valid].astype(np.float64),
                    idxs[g][valid].astype(np.int64), qn.n_rows > kop, dead_rows,
                ))
        return pending


def _capture_pending(monkeypatch) -> list:
    """Every increment's pending entries, as ``_quant_finalize`` gets them."""
    seen = []
    finalize = ECPIndex._quant_finalize

    def capturing(idx, pending, agg):
        seen.append([
            (qs.q.tobytes(), key, R, d, i, truncated, dead_rows)
            for qs, key, _, R, d, i, truncated, dead_rows in pending
        ])
        return finalize(idx, pending, agg)

    monkeypatch.setattr(ECPIndex, "_quant_finalize", capturing)
    return seen


def _count_launches(monkeypatch) -> list:
    """The leaf sizes of every grouped-kernel launch, one list a launch."""
    launches = []
    call = ops.grouped_distance_topk

    def counting(q, codes, scales, offsets, n_rows, *a, **kw):
        launches.append(list(n_rows))
        return call(q, codes, scales, offsets, n_rows, *a, **kw)

    monkeypatch.setattr(ops, "grouped_distance_topk", counting)
    return launches


def _session(blobs, case):
    """The (ids, dists) pages of one case."""
    if case == "tombstones-exclude":
        idx = open_index(blobs["tomb"], quantized=True)
        Q = _queries(4)
        excl = {int(i) for i in open_index(blobs["tomb"]).search(Q, 5, b=2).ids.ravel() if i >= 0}
        r = idx.search(Q, 15, b=6, exclude=excl)
        pages = [r, r.query.next(40)]
    elif case == "single":
        idx = open_index(blobs["plain"], quantized=True)
        pages = [idx.search(q, 20, b=8) for q in _queries(3)]
    elif case == "more":
        idx = open_index(blobs["plain"], quantized=True)
        r = idx.search(_queries(1)[0], 5, b=1)
        pages = [r, r.query.next(300)]
    else:  # batch8
        idx = open_index(blobs["plain"], quantized=True)
        r = idx.search(_queries(8), 25, b=6)
        pages = [r, r.query.next(25)]
    out = [(np.asarray(p.ids), np.asarray(p.dists)) for p in pages]
    idx.close()
    return out


def _same_pending(a, b):
    assert len(a) == len(b)
    for inc_a, inc_b in zip(a, b):
        assert len(inc_a) == len(inc_b)
        for ua, ub in zip(inc_a, inc_b):
            assert ua[:3] == ub[:3] and ua[5] == ub[5]
            np.testing.assert_array_equal(ua[3], ub[3])
            np.testing.assert_array_equal(ua[4], ub[4])
            assert (ua[6] is None) == (ub[6] is None)
            if ua[6] is not None:
                np.testing.assert_array_equal(ua[6], ub[6])


@pytest.mark.parametrize("case", ["single", "more", "batch8", "tombstones-exclude"])
def test_deferred_scans_match_one_launch_per_round(blobs, case, monkeypatch):
    with monkeypatch.context() as m:
        pending_ref = _capture_pending(m)
        launches_ref = _count_launches(m)
        _PerRound(m)
        ref = _session(blobs, case)
    with monkeypatch.context() as m:
        pending = _capture_pending(m)
        launches = _count_launches(m)
        got = _session(blobs, case)
    assert len(got) == len(ref)
    for (ids, dists), (ids_ref, dists_ref) in zip(got, ref):
        np.testing.assert_array_equal(ids, ids_ref)
        np.testing.assert_array_equal(dists, dists_ref)
    _same_pending(pending, pending_ref)
    if case == "more":  # the continuation opened leaves of its own
        assert len(pending) == 2 and pending[1]
    groups = sum(len(p) for p in pending)
    assert sum(map(len, launches)) == sum(map(len, launches_ref)) == groups
    assert len(launches) < len(launches_ref)


def test_each_unit_reads_as_its_own_launch_at_its_kop(blobs, monkeypatch):
    """Units of mixed widths (a batch whose rows sit at different rerank
    depths) share launches; each unit's entry is still its own launch's."""
    from repro.core.api import SearchStats

    seen = []
    scan = ECPIndex._quant_scan

    def keeping(idx, units, rounds, agg):
        seen.append(list(units))
        return scan(idx, units, rounds, agg)

    monkeypatch.setattr(ECPIndex, "_quant_scan", keeping)
    idx = open_index(blobs["plain"], quantized=True)
    idx.search(_queries(6, seed=8), 5, b=6)
    units = [u[:4] + ((32, 64, u[2].n_rows)[j % 3],) + u[5:] for j, u in enumerate(seen[0])]
    assert any(u[2].n_rows > u[4] for u in units)  # some lists are cut short
    pending = idx._quant_scan(units, 1, SearchStats())
    for (qs, key, qn, R, kop, _), got in zip(units, pending):
        d, i = ops.grouped_distance_topk(
            qs.q[None], qn.codes[None], np.array([qn.scale], np.float32),
            np.array([qn.offset], np.float32), np.array([qn.n_rows], np.int32),
            kop, idx.info.metric, idx._qformat,
        )
        valid = i[0] >= 0
        np.testing.assert_array_equal(got[4], d[0][valid].astype(np.float64))
        np.testing.assert_array_equal(got[5], i[0][valid].astype(np.int64))
        assert got[6] == (qn.n_rows > kop)
    idx.close()


@pytest.mark.parametrize("k,b", [(20, 20), (600, 2)], ids=["b20", "b-doubles"])
def test_a_query_launches_groups_of_at_most_eight(blobs, k, b, monkeypatch):
    launches = _count_launches(monkeypatch)
    idx = open_index(blobs["plain"], quantized=True)
    st = idx.search(_queries(1, seed=3)[0], k, b=b, mx_inc=4).stats
    widths = [len(rows) for rows in launches]
    idx.close()
    units = st.leaves_opened - st.fp_leaf_scans
    assert st.kernel_groups == units == sum(widths)
    assert st.kernel_launches == len(widths) == math.ceil(units / 8)
    assert max(widths) <= 8
    sizes = [n for rows in launches for n in rows]
    assert sizes == sorted(sizes, reverse=True)  # largest leaves first
    if b == 2:
        assert st.increments > 0 and units > 8  # b doubled past one launch


@pytest.mark.parametrize("B,b", [(32, 4), (128, 8)])
def test_a_batch_launches_no_more_often_than_it_had_rounds(blobs, B, b, monkeypatch):
    launches = _count_launches(monkeypatch)
    idx = open_index(blobs["plain"], quantized=True)
    st = idx.search(_queries(B, seed=B), 30, b=b).query.batch_stats
    widths = [len(rows) for rows in launches]
    idx.close()
    assert st.kernel_groups == sum(widths) > 8 * st.rounds  # G = 8 would launch too often
    assert st.kernel_launches == len(widths) <= st.rounds
    assert max(widths) <= 128


@pytest.mark.parametrize("n", [1, 100, 455])
def test_one_group_and_five_groups_pad_alike(n):
    assert grouped.padded_shape(1, n) == grouped.padded_shape(5, n) == (8, -(-n // 128) * 128)


def test_a_one_group_warm_up_compiles_the_launch_of_five():
    rng = np.random.default_rng(2)

    def launch(G, n=100, k=16):
        codes = rng.integers(-127, 128, (G, n, DIM)).astype(np.int8)
        return grouped.grouped_distance_topk_pallas(
            rng.standard_normal((G, DIM)).astype(np.float32), codes,
            np.full(G, 0.01, np.float32), np.zeros(G, np.float32), np.full(G, n, np.int32),
            k, "l2", "int8", interpret=True,
        )

    launch(1)
    compiled = grouped._grouped_call._cache_size()
    d, i = launch(5)
    assert grouped._grouped_call._cache_size() == compiled
    assert isinstance(d, np.ndarray) and d.shape == i.shape == (5, 16)


def test_a_wider_kop_lists_the_narrower_lists_prefix():
    """What lets a small leaf share a launch at a wider kop and a larger
    N: the kernel orders by (distance, row), ties included."""
    rng = np.random.default_rng(4)
    G, n = 3, 70
    codes = rng.integers(-3, 4, (G, n, DIM)).astype(np.int8)  # many tied rows
    q = rng.standard_normal((G, DIM)).astype(np.float32)
    prm = (np.full(G, 0.5, np.float32), np.zeros(G, np.float32), np.full(G, n, np.int32))
    d8, i8 = grouped.grouped_distance_topk_pallas(q, codes, *prm, 8, "l2", "int8", interpret=True)
    wide = np.zeros((G, 200, DIM), np.int8)
    wide[:, :n] = codes
    d24, i24 = grouped.grouped_distance_topk_pallas(q, wide, *prm, 24, "l2", "int8", interpret=True)
    np.testing.assert_array_equal(d8, d24[:, :8])
    np.testing.assert_array_equal(i8, i24[:, :8])
