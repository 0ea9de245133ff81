"""The one traffic generator.  A mix is a data file under `traffic/`
that sets its parameters; this module turns it, with the seed, into a
plan of requests and drives the plan against the service.

  kind "closed"  `clients` threads; each sends its next request as soon
                 as its previous one is answered, until the window
                 closes.  `max_rate_qps` bounds the query rows a run
                 encrypts ahead (rows per second of window).
  kind "open"    `rate_per_s` requests per second, due at the times of a
                 Poisson process (the count fixed at rate * seconds,
                 so every seed sends the same amount of work); a fixed
                 pool of `senders` threads sends each at its due time.

Every request carries `queries_per_request` query rows, drawn uniformly
from the pool of plaintext queries, each encrypted afresh.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time

import numpy as np

from .data import seed_words

PENDING, OK, REFUSED, ERROR = 0, 1, 2, 3
KINDS = ("closed", "open")


@dataclasses.dataclass
class Plan:
    kind: str
    nq: int                       # query rows per request
    n_requests: int               # requests encrypted ahead
    qrows: np.ndarray             # (n_requests, nq) pool query of each row
    offsets: np.ndarray | None    # open loop: due time - window start
    workers: int                  # client or sender threads


def validate(traffic: dict) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    need = {"closed": ("clients", "max_rate_qps"),
            "open": ("rate_per_s", "senders")}[kind]
    missing = [k for k in need + ("queries_per_request",)
               if k not in traffic]
    if missing:
        raise KeyError(f"traffic file lacks {missing}")


def make_plan(traffic: dict, n_pool: int, seed: int,
              seconds: float) -> Plan:
    validate(traffic)
    nq = int(traffic["queries_per_request"])
    rng = np.random.default_rng(seed_words(seed, 3)[2])
    if traffic["kind"] == "closed":
        rows = math.ceil(float(traffic["max_rate_qps"]) * seconds)
        n_req = max(1, -(-rows // nq))
        offsets = None
        workers = int(traffic["clients"])
    else:
        n_req = max(1, round(float(traffic["rate_per_s"]) * seconds))
        # a Poisson process given its count: sorted uniform due times
        offsets = np.sort(rng.uniform(0.0, seconds, n_req))
        workers = int(traffic["senders"])
    qrows = rng.integers(0, n_pool, size=(n_req, nq))
    return Plan(traffic["kind"], nq, n_req, qrows, offsets, workers)


@dataclasses.dataclass
class Log:
    """What happened to each request of a plan (times: time.monotonic)."""
    t_due: np.ndarray
    t_send: np.ndarray
    t_done: np.ndarray
    status: np.ndarray
    ids: list
    exhausted: bool = False       # a closed loop ran out of requests

    @classmethod
    def empty(cls, n: int) -> "Log":
        nan = np.full(n, np.nan)
        return cls(nan.copy(), nan.copy(), nan.copy(),
                   np.zeros(n, np.int8), [None] * n)

    def sent(self) -> np.ndarray:
        return ~np.isnan(self.t_send)


def _serve_one(submit, req, log: Log, i: int, t_due: float):
    t_send = time.monotonic()
    log.t_send[i] = t_send
    log.t_due[i] = t_send if math.isnan(t_due) else t_due
    try:
        res = submit(req)
    except Exception as exc:       # noqa: BLE001 — recorded per request
        log.status[i] = REFUSED if type(exc).__name__ == "QueueFullError" \
            else ERROR
        return
    log.t_done[i] = time.monotonic()
    log.ids[i] = res
    log.status[i] = OK


def drive(plan: Plan, submit, make_request, t0: float, seconds: float,
          *, join_timeout: float = 240.0) -> Log:
    """Run the plan from window start `t0` (time.monotonic, a moment
    ahead, so that every thread has started by then).  `submit` takes
    what `make_request(i)` returns and gives the answer's ids.  Returns
    once every sent request is answered or has failed."""
    log = Log.empty(plan.n_requests)
    t_end = t0 + seconds
    counter = itertools.count()
    lock = threading.Lock()

    def claim() -> int:
        with lock:
            return next(counter)

    def until_start():
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)

    def closed_client():
        until_start()
        while True:
            if time.monotonic() >= t_end:
                return
            i = claim()
            if i >= plan.n_requests:
                log.exhausted = True
                return
            _serve_one(submit, make_request(i), log, i, math.nan)

    def open_sender():
        until_start()
        while True:
            i = claim()
            if i >= plan.n_requests:
                return
            req = make_request(i)
            due = t0 + float(plan.offsets[i])
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            _serve_one(submit, req, log, i, due)

    body = closed_client if plan.kind == "closed" else open_sender
    threads = [threading.Thread(target=body, name=f"bench-client-{j}",
                                daemon=True) for j in range(plan.workers)]
    for t in threads:
        t.start()
    deadline = t_end + join_timeout
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        raise RuntimeError("requests still unanswered "
                           f"{join_timeout:.0f} s after the window closed")
    return log
