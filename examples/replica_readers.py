"""Multi-process replica serving over ONE blob file (paper §6.2 taken to
its logical end): N read-only reader processes + 1 writer process share a
single ``index.blob``; no sockets, no daemon — the FILE is the interface.

The writer mutates (inserts, deletes, one final compaction) and every
mutation commits through ``core/lifecycle.publish_generation``: a single
header ``pwrite`` that publishes the bumped ``generation`` together with
the new counts/registry/tombstones.  Readers poll that generation with
``refresh()`` and re-search.  The invariants this demo asserts — per
reader, from a separate process:

  * the raw blob header is NEVER torn: magic + length framing + JSON
    always parse, at any poll instant, mid-burst or not;
  * the observed generation sequence is monotonically non-decreasing;
  * every observed generation was actually published by the writer
    (no phantom states) — checked post-hoc against the writer's log;
  * searches stay available throughout, and any transiently-invalid
    result (a reader one generation stale can catch the writer reusing
    a slot its view still references — cross-process readers hold no
    pins) heals on ``refresh()`` + retry while the writer is live;
  * once the writer has exited, a final refresh + search is STRICT:
    every returned id must be one the final generation can contain.

Run it::

    PYTHONPATH=src python examples/replica_readers.py            # full demo
    PYTHONPATH=src python examples/replica_readers.py --smoke    # CI-sized

Exit code 0 = every invariant held in every process.

One process per accelerator: the build and the writer's inserts run jitted
JAX code, and a chip belongs to the first process that touches it.  So the
parent never imports JAX: a spawned child builds the blob and exits before
the writer starts, and the readers' plain searches stay in numpy (each
reader checks that it never initialised a JAX backend).
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time
from pathlib import Path

import numpy as np

DIM = 16
MAGIC = b"ECPBLOB1"


# ------------------------------------------------------------- header peek
def peek_header(blob_path: str) -> dict:
    """Read the raw blob header the way an external observer would: one
    open, one read, parse.  Raises if the header is torn."""
    with open(blob_path, "rb") as f:
        head = f.read(16)
        if head[:8] != MAGIC:
            raise AssertionError(f"torn header: bad magic {head[:8]!r}")
        hlen = int.from_bytes(head[8:16], "little")
        raw = f.read(hlen)
    if len(raw) != hlen:
        raise AssertionError(f"torn header: short read {len(raw)} < {hlen}")
    return json.loads(raw)  # a torn JSON body raises here


# ------------------------------------------------------------------ writer
def writer_proc(blob_path: str, log_path: str, n_rounds: int, batch: int) -> None:
    from repro.core import open_index

    rng = np.random.default_rng(1234)
    fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)
    try:
        with open_index(blob_path, mode="file", backend="blob") as idx:
            os.write(fd, f"{idx.info.generation}\n".encode())  # initial state
            next_id = idx.info.next_id
            for r in range(n_rounds):
                vecs = rng.normal(size=(batch, DIM)).astype(np.float32)
                ids = list(range(next_id, next_id + batch))
                next_id += batch
                res = idx.insert(vecs, ids=ids)
                os.write(fd, f"{res['generation']}\n".encode())
                if r % 3 == 2:  # tombstone a few of the rows just added
                    idx.delete(ids[: batch // 4])
                    os.write(fd, f"{idx.info.generation}\n".encode())
                time.sleep(0.01)
            # structural rewrite: compaction swaps the file via os.replace;
            # readers must ride through it on refresh()
            idx.compact()
            os.write(fd, f"{idx.info.generation}\n".encode())
    finally:
        os.close(fd)


# ------------------------------------------------------------------ reader
def reader_proc(
    blob_path: str, log_path: str, stop_path: str, poll_s: float
) -> None:
    from repro.core import open_index

    fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)

    def probe(idx, q, *, tries: int, pause: float) -> None:
        """One validated search.  A reader whose view is a generation
        stale can catch the writer recycling a slot its view still
        references (cross-process readers hold no pins): the symptom is
        either a search error on torn node bytes or out-of-range result
        ids.  While the writer is live (``tries > 1``) that must HEAL on
        refresh + retry; at quiescence (``tries == 1``) it must not
        happen at all."""
        err = None
        for t in range(tries):
            if t:
                time.sleep(pause)
                idx.refresh()
            try:
                rs = idx.search(q, k=5, b=4)
            except (KeyError, ValueError, IndexError) as e:
                err = f"search raised {e!r}"
                continue
            bad = [rid for _, rid in rs.pairs() if not 0 <= rid < idx.info.next_id]
            if not bad:
                return
            err = f"ids {bad} impossible"
        raise AssertionError(
            f"{err} at generation {idx.info.generation}"
            + (" after writer exit" if tries == 1 else " even after refresh+retry")
        )

    try:
        with open_index(blob_path, mode="file", backend="blob") as idx:
            q = np.zeros(DIM, dtype=np.float32)
            last = -1
            while True:
                writer_done = os.path.exists(stop_path)
                # 1. the raw file must parse at ANY instant
                hdr = peek_header(blob_path)
                raw_gen = int(hdr["info"]["generation"])
                assert raw_gen >= last, f"raw header went backwards: {raw_gen} < {last}"
                # 2. the library-level view: poll generation via refresh()
                idx.refresh()
                gen = idx.info.generation
                assert gen >= last, f"refresh went backwards: {gen} < {last}"
                last = gen
                os.write(fd, f"{gen}\n".encode())
                # 3. the observed state answers queries (see probe())
                probe(idx, q, tries=1 if writer_done else 6, pause=poll_s)
                if writer_done:
                    break
                time.sleep(poll_s)
    finally:
        os.close(fd)
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized(), "a reader took the writer's chip"


# ------------------------------------------------------------------- build
def build_proc(root: str) -> None:
    from repro.core import ECPBuildConfig, build_index, convert
    from repro.data import clustered_vectors

    data, _ = clustered_vectors(0, n=1500, dim=DIM, n_clusters=12)
    build_index(data, f"{root}/idx", ECPBuildConfig(levels=2, cluster_cap=64))
    convert(f"{root}/idx", f"{root}/index.blob")


# ----------------------------------------------------------------- harness
def run(n_readers: int = 3, n_rounds: int = 12, batch: int = 32) -> dict:
    import tempfile

    ctx = mp.get_context("spawn")  # clean children: no inherited locks/fds
    with tempfile.TemporaryDirectory() as td:
        td = Path(td)
        builder = ctx.Process(target=build_proc, args=(str(td),))
        builder.start()
        builder.join(timeout=120)
        assert builder.exitcode == 0, f"build failed: exit {builder.exitcode}"
        blob = str(td / "index.blob")
        assert "jax" not in sys.modules, "the parent must leave the chip to the writer"
        stop = str(td / "STOP")
        wlog = str(td / "published.log")
        rlogs = [str(td / f"reader_{i}.log") for i in range(n_readers)]

        readers = [
            ctx.Process(target=reader_proc, args=(blob, rlogs[i], stop, 0.005))
            for i in range(n_readers)
        ]
        writer = ctx.Process(target=writer_proc, args=(blob, wlog, n_rounds, batch))
        for p in readers:
            p.start()
        writer.start()
        writer.join(timeout=120)
        assert writer.exitcode == 0, f"writer failed: exit {writer.exitcode}"
        Path(stop).touch()  # writer is done; let readers observe the final state
        for p in readers:
            p.join(timeout=60)
            assert p.exitcode == 0, f"reader failed: exit {p.exitcode}"

        published = [int(x) for x in Path(wlog).read_text().split()]
        final_gen = published[-1]
        summary = {"published": len(published), "final_gen": final_gen, "readers": []}
        for i, rl in enumerate(rlogs):
            seen = [int(x) for x in Path(rl).read_text().split()]
            assert seen, f"reader {i} observed nothing"
            assert all(a <= b for a, b in zip(seen, seen[1:])), (
                f"reader {i} saw a non-monotonic sequence: {seen}"
            )
            phantom = set(seen) - set(published)
            assert not phantom, (
                f"reader {i} observed generations the writer never "
                f"published (torn/phantom state): {sorted(phantom)}"
            )
            summary["readers"].append({"observations": len(seen), "distinct": len(set(seen))})
        return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--readers", type=int, default=3)
    args = ap.parse_args()
    if args.smoke:
        summary = run(n_readers=2, n_rounds=6, batch=16)
    else:
        summary = run(n_readers=args.readers)
    print(
        f"replica demo OK: {summary['published']} published generations "
        f"(final={summary['final_gen']}); "
        + "; ".join(
            f"reader{i}: {r['observations']} polls, {r['distinct']} distinct gens"
            for i, r in enumerate(summary["readers"])
        )
    )
    print("no reader ever observed a torn or unpublished generation")


if __name__ == "__main__":
    main()
