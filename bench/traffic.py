"""The one traffic generator: reads a mix's parameters and drives the
service with them.

A mix (``traffic/<name>.json``) gives:

- ``loop``: ``"closed"`` (``callers`` threads, each sending its next
  ``batch`` of queries through ``search_many`` as soon as the last one is
  answered) or ``"open"`` (single queries through ``search`` at fixed
  due times, ``rate_qps`` Poisson arrivals, ``workers`` threads to carry
  them, so a slow answer delays no later send);
- ``classes``: request classes with their ``share``, ``n_hops`` and an
  optional ``where`` predicate ``[column, op, value]``;
- ``service``: how the ``RetrievalService`` is set up (micro-batching,
  its window and largest batch);
- ``query_noise``, the noise added to the corpus rows that make queries,
  and the sample sizes of the output check.

Every seed gets the same work: the open loop's schedule (its Poisson gaps,
scaled to the window, and the class of each arrival, dealt in fixed
counts) is one fixed draw, the same for every run. The seed picks which
corpus rows become queries and the noise on them.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional

import numpy as np

SCHEDULE_SEED = 20240917       # the one draw of the open-loop schedule
LATE_WAIT_S = 60.0             # how long past the close an answer may come


@dataclasses.dataclass
class Request:
    cls: int                   # index into the mix's classes
    q0: int                    # first query row of this request
    n: int                     # queries in it
    due: float = 0.0           # seconds after the window opened
    sent: float = math.nan
    done: float = math.nan
    scores: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    requests: List[Request]
    elapsed_s: float           # open to the last answer
    seconds: float             # the window asked for

    @property
    def answered(self) -> List[Request]:
        return [r for r in self.requests if r.ids is not None]

    def queries_answered(self) -> int:
        return sum(r.n for r in self.answered)

    def latencies_ms(self) -> np.ndarray:
        """Due time to answer, per request; a request that failed or was
        never answered counts as infinitely late."""
        return np.array([(r.done - r.due) * 1e3 if r.ids is not None
                         else math.inf for r in self.requests])

    def lateness_ms(self) -> np.ndarray:
        """How late the generator sent each request after its due time."""
        sent = np.array([r.sent - r.due for r in self.requests
                         if not math.isnan(r.sent)])
        return sent * 1e3


def nearest_rank(values: np.ndarray, pct: float) -> float:
    """The ``pct`` percentile by nearest rank (a value that occurred)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[max(int(math.ceil(pct / 100.0 * v.size)) - 1, 0)])


def class_counts(shares: List[float], n: int) -> np.ndarray:
    """Whole counts in proportion to ``shares`` that sum to ``n``."""
    raw = np.asarray(shares, np.float64) * n / float(np.sum(shares))
    out = np.floor(raw).astype(np.int64)
    out[np.argsort(-(raw - out), kind="stable")[:n - int(out.sum())]] += 1
    return out


def open_schedule(mix: dict, seconds: float,
                  rate: Optional[float] = None) -> List[Request]:
    """The open loop's requests: round(rate·seconds) arrivals whose
    exponential gaps are scaled to fill the window, with classes dealt in
    fixed counts; one draw, the same for every run."""
    rate = float(mix["rate_qps"] if rate is None else rate)
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng(SCHEDULE_SEED)
    gaps = rng.exponential(1.0, n)
    due = np.cumsum(gaps) - gaps[0]
    if n > 1:
        due *= seconds * (n - 1) / n / max(due[-1], 1e-12)
    cls = np.repeat(np.arange(len(mix["classes"])),
                    class_counts([c["share"] for c in mix["classes"]], n))
    cls = rng.permutation(cls)
    return [Request(int(c), i, 1, float(t)) for i, (c, t)
            in enumerate(zip(cls, due))]


def run_closed(call: Callable, mix: dict, seconds: float,
               annotate: Callable) -> Window:
    """``callers`` threads each send their next batch of ``batch`` queries
    as soon as the last is answered, until ``seconds`` have passed; the
    window closes at the last answer. ``call(cls, q0, n)`` serves rows
    [q0, q0 + n) of the query pool and returns (scores, ids)."""
    batch, pool = int(mix["batch"]), int(mix["query_pool"])
    lock = threading.Lock()
    reqs: List[Request] = []
    state = {"next": 0}
    t0 = time.perf_counter()

    def caller():
        while time.perf_counter() - t0 < seconds:
            with lock:
                q0 = state["next"] % pool
                state["next"] += batch
                r = Request(0, q0, batch, time.perf_counter() - t0)
                reqs.append(r)
            r.sent = r.due
            _serve(call, r, t0, annotate, "bench.search_many")

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(int(mix["callers"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + LATE_WAIT_S)
    return _close(reqs, t0, seconds)


def run_open(call: Callable, reqs: List[Request], workers: int,
             seconds: float, annotate: Callable) -> Window:
    """Sends each request at its due time from this thread onto a pool of
    ``workers`` threads, whatever earlier requests are doing; waits for
    answers until ``LATE_WAIT_S`` past the close."""
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        futures = []
        for r in reqs:
            wait = r.due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            r.sent = time.perf_counter() - t0
            futures.append(pool.submit(_serve, call, r, t0, annotate,
                                       "bench.search"))
        concurrent.futures.wait(
            futures, timeout=max(seconds + LATE_WAIT_S
                                 - (time.perf_counter() - t0), 0.0))
        for f in futures:
            f.cancel()
        window = _close(reqs, t0, seconds)
    return window


def _serve(call, r: Request, t0: float, annotate, label: str) -> None:
    try:
        with annotate(label):
            scores, ids = call(r.cls, r.q0, r.n)
    except Exception as e:                  # a failed request, counted
        r.error = repr(e)
        return
    r.done = time.perf_counter() - t0
    r.scores = scores
    r.ids = ids                 # last: an answer with ids has its time


def _close(reqs: List[Request], t0: float, seconds: float) -> Window:
    """The window as it stands now: answers that come later than
    ``LATE_WAIT_S`` past the close never came."""
    deadline = seconds + LATE_WAIT_S
    taken = []
    for r in list(reqs):
        if r.ids is not None and r.done <= deadline:
            taken.append(dataclasses.replace(r))
        else:
            taken.append(dataclasses.replace(r, scores=None, ids=None,
                                             done=math.nan))
    elapsed = max([r.done for r in taken if r.ids is not None],
                  default=time.perf_counter() - t0)
    return Window(taken, elapsed, seconds)
