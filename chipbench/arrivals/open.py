"""Open loop: independent clients on a fixed schedule.

Parameters of the traffic file:

``rate_per_s``
    the offered rate.  The run offers ``round(rate_per_s * seconds)``
    requests; the gaps between them are the quantiles of an exponential
    distribution of mean ``1/rate_per_s`` (a Poisson stream) in one order
    that no seed changes, so every seed offers the same arrivals and the
    same number of requests of each kernel; the seed orders the kernels
    and draws the data.
``mix``
    kernel name -> weight; requests per kernel by largest remainder.
``clients``
    submitting threads.

Each request is submitted at its due time by one of the client threads,
whatever the server is doing, and its latency runs from that due time.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from chipbench import drive
from chipbench.traffic import Request, apportion, poisson_gaps, rng_for

#: Granularity at which the collector notices results, and its allowance
#: per outstanding request (a backlog of 1000 is scanned every 5 ms).
_POLL_S = 0.001
_SCAN_S = 5e-6
#: Lead between the end of set-up and the first due time.
_LEAD_S = 0.05


def kernels(params: dict) -> List[str]:
    return list(params["mix"])


class Arrivals:
    def __init__(self, traffic, seconds: float):
        self.traffic = traffic
        params = traffic.params
        rate = float(params["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        counts = apportion(params["mix"], n)
        names = traffic.kernels
        gaps = rng_for(0, 2).permutation(poisson_gaps(n, rate))
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        order = rng_for(traffic.seed, 2).permutation(
            np.repeat(np.arange(len(names)), [counts[k] for k in names]))
        pools = traffic.instances(counts)
        used = {k: 0 for k in names}
        self.requests: List[Request] = []
        for i, (t, k) in enumerate(zip(due, order)):
            name = names[k]
            pool = pools[name]
            inst = pool[used[name] % len(pool)]
            used[name] += 1
            self.requests.append(Request(i, name, inst, float(t)))
        self.clients = max(1, int(params.get("clients", 1)))
        self.seconds = seconds

    def submissions(self):
        return [(r.instance, 1) for r in self.requests]

    def window(self, sched, counter, spans, on_start=None) -> drive.Window:
        reqs = self.requests
        n_clients = self.clients
        sampler = self.traffic.sampler()
        recs = [drive.Record(r) for r in reqs]
        pending: Dict[int, object] = {}
        lock = threading.Lock()
        senders_left = [n_clients]
        stats_before = drive.scheduler_stats(sched)
        if on_start is not None:
            on_start()
        counter.armed = True
        start = time.perf_counter() + _LEAD_S
        close = start + self.seconds
        for r, rec in zip(reqs, recs):
            rec.due = start + r.due

        def sender(k: int) -> None:
            try:
                for i in range(k, len(reqs), n_clients):
                    r, rec = reqs[i], recs[i]
                    delay = rec.due - time.perf_counter()
                    if delay > 0:
                        with spans("client.sleep"):
                            time.sleep(delay)
                    rec.submitted = time.perf_counter()
                    try:
                        with spans("client.submit"):
                            t = sched.submit(r.instance.program,
                                             r.instance.memory)
                    except Exception as e:
                        rec.error = f"{type(e).__name__}: {e}"
                        continue
                    finally:
                        rec.accepted = time.perf_counter()
                    with lock:
                        pending[i] = t
            finally:
                with lock:
                    senders_left[0] -= 1

        threads = [threading.Thread(target=sender, args=(k,), daemon=True,
                                    name=f"bench-client-{k}")
                   for k in range(n_clients)]
        for th in threads:
            th.start()
        give_up = close + drive.LATE_S
        time.sleep(max(0.0, start - time.perf_counter()))
        with spans(drive.WINDOW), spans("client.wait"):
            while True:
                with lock:
                    if not pending and senders_left[0] == 0:
                        break
                    oldest = next(iter(pending.values()), None)
                    backlog = len(pending)
                if time.perf_counter() > give_up:
                    break
                if oldest is None:
                    time.sleep(_POLL_S)
                    continue
                # Wait for the oldest answer, at most one poll interval,
                # which grows with the backlog so that scanning it stays a
                # small share of the host's time however far the server
                # falls behind.
                try:
                    oldest.result(timeout=max(_POLL_S, backlog * _SCAN_S))
                except Exception:
                    pass
                with lock:
                    done = [(i, t) for i, t in pending.items() if t.done()]
                    for i, _ in done:
                        del pending[i]
                now = time.perf_counter()
                for i, t in done:
                    drive.finish(recs[i], t, sampler, now)
        end = time.perf_counter()
        counter.armed = False
        for th in threads:
            th.join(timeout=1.0)
        for i, t in pending.items():
            t.cancel()
            recs[i].error = recs[i].error or "unanswered"
        return drive.Window(recs, start, close, end, stats_before,
                            drive.scheduler_stats(sched), counter.count,
                            sampler.items())
