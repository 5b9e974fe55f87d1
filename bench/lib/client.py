"""The open-loop client: sends each request when it is due, whatever the
server is doing, and times it from that due time to its answer.

Searches go through ``Server.submit`` (the server's worker pool).  A
session's ``more`` is due ``think`` seconds after its search's page came
back and runs on one of the client's own threads, since ``Server.more``
runs on its caller's thread.  Nothing is sent once the window has
closed; requests already sent are waited for, up to ``GRACE_S``: a cell
offered more than the server sustains ends its window with a queue.
"""
from __future__ import annotations

import copy
import heapq
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

GRACE_S = 150.0  # how long answers due in the window are waited for
CLIENT_THREADS = 8  # threads that run more() and close()


@dataclass
class Request:
    kind: str               # "search" | "more"
    due: float              # perf_counter() time it was due
    query: int              # index into the query pool
    session: "Session"
    sent: float | None = None
    done: float | None = None
    ids: np.ndarray | None = None
    dists: np.ndarray | None = None
    stats: object = None    # the page's SearchStats, as they stood when it came back
    error: BaseException | None = None

    @property
    def latency_s(self) -> float:
        return self.done - self.due


@dataclass
class Session:
    query: int
    think_s: float
    sid: int | None = None
    requests: list = field(default_factory=list)


class OpenLoop:
    """One window of open-loop traffic against ``srv``."""

    def __init__(self, srv, pool: np.ndarray, mix: dict, span=None):
        self.srv = srv
        self.pool = pool
        self.k = int(mix["k"])
        self.b = int(mix["b"])
        self.more_k = int(mix.get("more_k", 0))
        self._span = span or (lambda name: nullcontext())
        self._cond = threading.Condition()
        self._heap: list = []
        self._seq = 0
        self._closed = False
        self._inflight = 0
        self._clients = ThreadPoolExecutor(CLIENT_THREADS, thread_name_prefix="bench-client")
        self.requests: list[Request] = []
        self.sessions: list[Session] = []

    # ------------------------------------------------------------ schedule
    def _push(self, req: Request) -> None:
        # under self._cond
        heapq.heappush(self._heap, (req.due, self._seq, req))
        self._seq += 1
        self._cond.notify()

    def run(self, sched, t0: float, seconds: float) -> None:
        """Send ``sched``'s sessions with the window opening at ``t0``;
        returns once every request sent has been answered or the grace
        period is over."""
        self.t0, self.t_end = t0, t0 + seconds
        with self._cond:
            for due, q, think in zip(sched.due_s, sched.query, sched.think_s):
                s = Session(query=int(q), think_s=float(think))
                self.sessions.append(s)
                self._push(Request("search", t0 + float(due), int(q), s))
        while True:
            with self._cond:
                while True:
                    now = time.perf_counter()
                    head = self._heap[0][0] if self._heap else None
                    if head is not None and head <= now:
                        req = heapq.heappop(self._heap)[2]
                        break
                    if now >= self.t_end and (head is None or head >= self.t_end):
                        self._closed = True
                        req = None
                        break
                    wait = (head if head is not None else self.t_end) - now
                    with self._span("wait_arrival"):
                        self._cond.wait(min(wait, self.t_end - now) if now < self.t_end else wait)
            if req is None:
                break
            self._send(req)
        with self._cond:
            # mores due after the close are never sent: end their sessions
            for _, _, req in self._heap:
                self._clients.submit(self._close, req.session.sid)
            self._heap.clear()
        self._drain()

    def _send(self, req: Request, counted: bool = False) -> None:
        req.sent = time.perf_counter()
        with self._cond:
            self.requests.append(req)
            req.session.requests.append(req)
            if not counted:
                self._inflight += 1
        if req.kind == "search":
            try:
                fut = self.srv.submit(self.pool[req.query], self.k, b=self.b)
            except BaseException as e:  # refused at admission: never answered
                self._finish(req, error=e)
                return
            fut.add_done_callback(lambda f, r=req: self._searched(r, f))
        else:
            self._clients.submit(self._more, req)

    def _searched(self, req: Request, fut) -> None:
        done = time.perf_counter()
        try:
            rs, sid = fut.result()
        except BaseException as e:
            self._finish(req, error=e, done=done)
            return
        req.session.sid = sid
        follow = None
        if self.more_k:
            follow = Request("more", done + req.session.think_s, req.query, req.session)
        else:
            self._close(sid)
        self._finish(req, rs=rs, done=done, follow=follow)

    def _more(self, req: Request) -> None:
        try:
            with self._span("more"):
                rs = self.srv.more(req.session.sid, self.more_k)
        except BaseException as e:
            self._finish(req, error=e)
        else:
            self._finish(req, rs=rs, done=time.perf_counter())
        self._close(req.session.sid)

    def _close(self, sid: int) -> None:
        try:
            self.srv.close(sid)
        except Exception:
            pass  # already closed or evicted: nothing is held

    def _finish(self, req, *, rs=None, error=None, done=None, follow=None) -> None:
        if rs is not None:
            # a session's stats object keeps counting: keep this page's reading
            req.ids, req.dists, req.stats = rs.ids, rs.dists, copy.copy(rs.stats)
        req.error = error
        req.done = done if error is None else None
        with self._cond:
            if follow is not None:
                if not self._closed:
                    self._push(follow)
                    follow = None
                elif follow.due >= self.t_end:
                    self._clients.submit(self._close, req.session.sid)
                    follow = None
                else:
                    self._inflight += 1  # counted before the drain can end
            self._inflight -= 1
            self._cond.notify_all()
        if follow is not None:  # due inside the window, found after it closed
            self._send(follow, counted=True)

    def _drain(self) -> None:
        deadline = time.perf_counter() + GRACE_S
        with self._cond:
            while self._inflight and time.perf_counter() < deadline:
                self._cond.wait(deadline - time.perf_counter())
            hung = self._inflight
        self._clients.shutdown(wait=not hung, cancel_futures=bool(hung))

    # ------------------------------------------------------------- results
    def of(self, kind: str) -> list[Request]:
        return [r for r in self.requests if r.kind == kind]
