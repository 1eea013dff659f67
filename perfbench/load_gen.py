"""The one traffic generator. A mix is a data file ``traffic/<name>.json``
whose ``kind`` picks the loop here and whose other keys are its parameters.

``closed_loop``: ``callers`` threads share one ``IndexClient``; each sends a
request of ``rows_per_request`` fresh query rows, waits for the reply, and
sends the next. Callers that each wait for a reply are a closed loop
(choosing-metrics guide, section 5): retrieval workers that need their
neighbours before their next step. Every seed gives the same number of
callers and the same request sizes; only the query rows differ, drawn from
the cell's query pool in an order the seed fixes.

Caller i sends its first request ``i x stagger_s`` after caller 0. Callers
released together race for the scheduler's 2 ms window, and how the race
falls decides for the whole run which requests share a device window: 4
callers of 64 rows ran as 2+2 in one run and as 4 in the next, 191 against
235 queries/s on the same code (my chip run, PR 23). A stagger longer than
the scheduler's wait and far shorter than a launch puts every run into the
same pattern: the first caller's request runs alone, the others queue behind
it and share the next window, and so on in turn.

Requests are sent for ``seconds``; then nothing new is sent and the requests
in flight are allowed to finish. The window runs from the first send to the
last reply, so a rate taken over it counts all the work and all the time,
and is not quantised by how many merged device windows happened to finish
before a fixed instant.
"""

import itertools
import threading
import time

import numpy as np


class Result:
    """What one request saw. ``ok`` is false for a request that raised or
    whose reply had the wrong shape; such a request counts as failed and
    never towards a rate."""

    __slots__ = ("start", "end", "first_row", "rows", "ok", "scores", "ids", "error")

    def __init__(self, start, end, first_row, rows, ok, scores=None, ids=None,
                 error=None):
        self.start, self.end = start, end
        self.first_row, self.rows = first_row, rows
        self.ok, self.scores, self.ids, self.error = ok, scores, ids, error


def request_sizes(traffic, max_batch_rows=256):
    """Row counts a merged device window can have under this mix: any
    number of concurrent callers' requests, up to the scheduler's window.
    Warm-up sends one request of each size, so no shape is first met inside
    the measured window."""
    if traffic["kind"] != "closed_loop":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    rows = int(traffic["rows_per_request"])
    sizes = {j * rows for j in range(1, int(traffic["callers"]) + 1)
             if j == 1 or j * rows <= max_batch_rows}
    return sorted(sizes)


def search_once(client, index_id, k, q, first_row):
    """One request through the client; a Result either way."""
    start = time.perf_counter()
    try:
        scores, meta = client.search(q, k, index_id)
        end = time.perf_counter()
    except Exception as e:  # any failure is the request's, and is counted
        return Result(start, time.perf_counter(), first_row, q.shape[0], False,
                      error=f"{type(e).__name__}: {e}")
    scores = np.asarray(scores)
    ok = (scores.shape == (q.shape[0], k) and len(meta) == q.shape[0]
          and all(len(row) == k for row in meta))
    ids = None
    if ok:
        ids = np.array([[-1 if m is None else m for m in row] for row in meta],
                       np.int64)
    return Result(start, end, first_row, q.shape[0], ok, scores, ids,
                  None if ok else "reply has the wrong shape")


def closed_loop(client, index_id, k, pool, traffic, seed, seconds):
    """Drive the mix for ``seconds``; returns (results, t_first_send,
    t_last_reply), times on ``time.perf_counter``."""
    callers = int(traffic["callers"])
    rows = int(traffic["rows_per_request"])
    stagger = float(traffic["stagger_s"])
    slots = pool.shape[0] // rows
    order = np.random.default_rng([int(seed), 3]).permutation(slots)
    lock = threading.Lock()
    turn = itertools.count()  # next() on it is atomic: one slot a request
    results = []
    gate = threading.Barrier(callers + 1)
    deadline = [None]

    def caller(number):
        mine = []
        gate.wait()
        time.sleep(number * stagger)
        while time.perf_counter() < deadline[0]:
            first = int(order[next(turn) % slots]) * rows
            mine.append(search_once(client, index_id, k, pool[first:first + rows], first))
        with lock:
            results.extend(mine)

    threads = [threading.Thread(target=caller, args=(i,), name=f"caller{i}",
                                daemon=True) for i in range(callers)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    deadline[0] = t0 + seconds
    gate.wait()
    for t in threads:
        t.join()
    t1 = max((r.end for r in results), default=time.perf_counter())
    return results, t0, t1


KINDS = {"closed_loop": closed_loop}


def drive(client, index_id, k, pool, traffic, seed, seconds):
    if traffic["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}; "
                         f"this generator has {sorted(KINDS)}")
    return KINDS[traffic["kind"]](client, index_id, k, pool, traffic, seed, seconds)
