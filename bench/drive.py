"""The traffic generator that drives the program's serving loop.

One general generator per kind of traffic, parameterised by a traffic
file.  The one kind so far is ``closed``: a fixed number of callers,
each sending its next query when its reply arrives.  It submits to the
real ``AsyncServingLoop`` on the wall clock; the engine the loop calls
is :class:`Engine`, which records every sealed batch.  Times are
``time.perf_counter()`` seconds.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Batch:
    t_start: float
    t_end: float
    size: int
    arities: np.ndarray
    counts: np.ndarray
    info: dict


class Engine:
    """The ``engine=`` the serving loop dispatches through: the program's
    ``serve_counts_device``, timed, with each batch's ``info`` kept.

    ``fault(cq, counts)`` rewrites the counts on their way back: the
    control (the reference in the program's place) and the self-tests'
    broken paths, which the comparison has to catch."""

    def __init__(self, serve, annotate, fault: Optional[Callable] = None):
        self._serve = serve
        self._annotate = annotate
        self._fault = fault
        self.batches: List[Batch] = []

    def __call__(self, cq):
        with self._annotate("bench.dispatch"):
            t0 = time.perf_counter()
            counts, info = self._serve(cq)
            t1 = time.perf_counter()
        counts = np.asarray(counts)
        if self._fault is not None:
            counts = self._fault(cq, counts)
        self.batches.append(Batch(t0, t1, cq.n_queries, np.asarray(cq.arities), counts, info))
        return counts, info


@dataclasses.dataclass
class Requests:
    """One row per request, in the order the requests were submitted
    (which is the order the loop dispatches them in)."""

    qid: np.ndarray  # index into the pool
    sent: np.ndarray
    reply: np.ndarray  # NaN: never answered
    count: np.ndarray  # -1: never answered


class _Log:
    def __init__(self):
        self.qid, self.sent = [], []
        self.reply, self.count = {}, {}

    def send(self, qid: int) -> int:
        i = len(self.qid)
        self.qid.append(qid)
        self.sent.append(time.perf_counter())
        return i

    def answer(self, i: int, count: int) -> None:
        self.reply[i] = time.perf_counter()
        self.count[i] = count

    def freeze(self) -> Requests:
        n = len(self.qid)
        reply = np.full(n, np.nan)
        count = np.full(n, -1, np.int64)
        for i, t in self.reply.items():
            reply[i] = t
            count[i] = self.count[i]
        return Requests(
            np.asarray(self.qid, np.int64),
            np.asarray(self.sent, np.float64),
            reply,
            count,
        )


async def _closed(loop, terms, callers: int, batch: int, t_end: float, log: _Log):
    """``callers`` callers share one stream: ``terms`` in order, cycled.
    After ``t_end`` no caller starts a new batch's worth of the stream,
    so every batch the loop seals is a whole one."""
    nxt = [0]

    def take() -> Optional[int]:
        k = nxt[0]
        if k % batch == 0 and time.perf_counter() >= t_end:
            return None
        nxt[0] = k + 1
        return k % len(terms)

    async def caller():
        while True:
            q = take()
            if q is None:
                return
            i = log.send(q)
            log.answer(i, await loop.submit(terms[q]))

    await asyncio.gather(*(caller() for _ in range(callers)))


async def drive(loop, traffic: dict, terms, t0: float, seconds: float, batch: int = 64,
                grace_s: float = 60.0) -> Requests:
    """Run one window of ``traffic`` against a started serving loop.

    Returns every request sent.  Requests still unanswered ``grace_s``
    after the window closed stay unanswered (NaN reply)."""
    log = _Log()
    kind = traffic["kind"]
    if kind != "closed":
        raise ValueError(f"unknown traffic kind {kind!r}")
    gen = _closed(loop, terms, int(traffic["callers"]), batch, t0 + seconds, log)
    task = asyncio.ensure_future(gen)
    wait = max(t0 + seconds + grace_s - time.perf_counter(), 0.0)
    done, _ = await asyncio.wait({task}, timeout=wait)
    if task in done:
        task.result()
    else:
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
    return log.freeze()
