"""Closed loop: one client submits a round of requests, waits for every
answer, and submits the next, until a round ends after the window's
seconds.  The window is those whole rounds, so a rate over it counts
every request and all the time they took.

Parameters of the traffic file:

``round``
    kernel name -> requests per round, submitted in this order.
``rounds_per_s`` (fresh pool)
    rounds of never-repeated programs built ahead, per second of the
    window.  Were they all used before the window's seconds are up, the
    window closes after the last of them (a shorter window, said on
    standard error), since a repeated program would be promoted and
    change the work.
"""
from __future__ import annotations

import math
import sys
import time
from typing import List

from chipbench import drive
from chipbench.traffic import Request


def kernels(params: dict) -> List[str]:
    return list(params["round"])


class Arrivals:
    def __init__(self, traffic, seconds: float):
        self.traffic = traffic
        self.seconds = seconds
        self.counts = dict(traffic.params["round"])
        self.rounds = None
        if traffic.fresh:
            self.rounds = max(1, math.ceil(
                float(traffic.params["rounds_per_s"]) * seconds))
            self.pools = traffic.instances(
                {k: n * self.rounds for k, n in self.counts.items()})
        else:
            self.pools = traffic.instances(self.counts)

    def submissions(self):
        # A fresh pool's instances are submitted once each; a fixed pool's
        # are taken again every few rounds.
        n = 1 if self.traffic.fresh else 2
        return [(i, n) for pool in self.pools.values() for i in pool]

    def round(self, r: int) -> List[Request]:
        if self.rounds is not None and r >= self.rounds:
            raise IndexError(f"only {self.rounds} rounds were built")
        out = []
        for name, count in self.counts.items():
            pool = self.pools[name]
            for j in range(count):
                inst = pool[(r * count + j) % len(pool)]
                out.append(Request(len(out), name, inst))
        return out

    def window(self, sched, counter, spans, on_start=None) -> drive.Window:
        sampler = self.traffic.sampler()
        recs: List[drive.Record] = []
        stats_before = drive.scheduler_stats(sched)
        if on_start is not None:
            on_start()
        counter.armed = True
        start = time.perf_counter()
        close = start + self.seconds
        r = 0
        with spans(drive.WINDOW):
            while time.perf_counter() < close:
                if self.rounds is not None and r >= self.rounds:
                    print(f"chipbench: all {self.rounds} rounds built were "
                          f"used; the window closes after "
                          f"{time.perf_counter() - start:.3f} s",
                          file=sys.stderr, flush=True)
                    break
                reqs = self.round(r)
                r += 1
                tickets = []
                with spans("client.submit"):
                    for q in reqs:
                        rec = drive.Record(q, submitted=time.perf_counter())
                        try:
                            tickets.append(sched.submit(q.instance.program,
                                                        q.instance.memory))
                        except Exception as e:
                            rec.error = f"{type(e).__name__}: {e}"
                            tickets.append(None)
                        rec.accepted = time.perf_counter()
                        recs.append(rec)
                give_up = time.perf_counter() + drive.LATE_S
                with spans("client.wait"):
                    for t, rec in zip(tickets, recs[-len(reqs):]):
                        if t is None:
                            continue
                        left = give_up - time.perf_counter()
                        try:
                            t.result(timeout=max(0.0, left))
                        except Exception:
                            pass
                        if t.done():
                            drive.finish(rec, t, sampler, time.perf_counter())
                        else:
                            t.cancel()
                            rec.error = "unanswered"
        end = time.perf_counter()
        counter.armed = False
        return drive.Window(recs, start, end, end, stats_before,
                            drive.scheduler_stats(sched), counter.count,
                            sampler.items())
