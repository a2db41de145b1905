"""The reduction of the program's own spans and counters
(``chipbench/serve_path.py``) against hand-computed numbers, on plain
events and on traces recorded on a TPU v5e chip; and the device
reduction (``trace_reduce``) of the trace recorded before the program had
spans, key by key."""
import gzip
import os
import types

import pytest

from chipbench import serve_path as sp
from chipbench import trace_reduce as tr
from chipbench.metrics import (d2h_kb_per_req, h2d_kb_per_req,
                               host_us_per_dispatch, idle_host_share,
                               lower_ms, queue_wait_ms, worker_busy_share)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE_READERS = (worker_busy_share, host_us_per_dispatch, idle_host_share,
                 lower_ms)


def _profile(name):
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, name), "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def _run(reduced=None, before=None, after=None, served=1):
    """What a reader sees of a run: the reduction already kept on it."""
    window = types.SimpleNamespace(stats_before=before or {},
                                   stats_after=after or {})
    return types.SimpleNamespace(
        trace={} if reduced is not None else None, serve_path=reduced,
        window=window, served=lambda: [None] * served)


def test_reduce_events_by_hand():
    """Window 100..200 ns.  The device runs 110-130, 150-160 and 190-230:
    idle 60 ns in 100-110, 130-150 and 160-190.  The worker: idle, window,
    group, dispatch, sync, fetch, resolve, idle, group, a promotion with
    a lowering nested in it, dispatch (cut at 200).  A client lowers
    140-165 with its VM tables 145-150.  Host work covers 112-125,
    140-165 and 170-200, so 10 + 25 of the 60 idle ns."""
    worker = [("sched.idle", 90, 105), ("sched.window", 105, 112),
              ("sched.group", 112, 118), ("sched.dispatch", 118, 125),
              ("sched.sync", 125, 150), ("sched.fetch", 150, 155),
              ("sched.resolve", 155, 158), ("sched.idle", 158, 170),
              ("sched.group", 170, 172), ("sched.promote", 172, 185),
              ("engine.lower", 175, 182), ("sched.dispatch", 185, 205)]
    client = [("engine.lower", 140, 165), ("vm.tables", 145, 150)]
    ops = {"/device:TPU:0": [(110, 130), (150, 160), (190, 230)]}
    got = sp.reduce_events(ops, [worker, client], (100, 200))
    ns = 1e-9
    want = {"sched.idle": (1, 17), "sched.window": (1, 7),
            "sched.group": (2, 8), "sched.dispatch": (2, 22),
            "sched.sync": (1, 25), "sched.fetch": (1, 5),
            "sched.resolve": (1, 3), "sched.promote": (1, 13),
            "engine.lower": (2, 32), "vm.tables": (1, 5)}
    self_ns = dict((k, v[1]) for k, v in want.items())
    self_ns.update({"sched.promote": 6, "engine.lower": 27})
    assert set(got["spans"]) == set(want)
    for name, (count, total) in want.items():
        rec = got["spans"][name]
        assert rec["count"] == count, name
        assert rec["total_s"] == pytest.approx(total * ns), name
        assert rec["self_s"] == pytest.approx(self_ns[name] * ns), name
    assert got["window_s"] == pytest.approx(100 * ns)
    assert got["worker_busy_s"] == pytest.approx(76 * ns)   # 112-158, 170-200
    assert got["idle_s"] == pytest.approx(60 * ns)
    assert got["idle_host_s"] == pytest.approx(35 * ns)

    run = _run(got)
    assert worker_busy_share.read(run) == pytest.approx(76.0)
    assert host_us_per_dispatch.read(run) == pytest.approx(
        (8 + 22 + 5 + 3) * ns / 2 * 1e6)
    assert idle_host_share.read(run) == pytest.approx(35 / 60 * 100)
    assert lower_ms.read(run) == pytest.approx(32 * ns / 2 * 1e3)


def test_counter_readers():
    before = {"queue_wait_s": 1.0, "taken": 10, "h2d_bytes": 0,
              "d2h_bytes": 2048}
    after = {"queue_wait_s": 1.5, "taken": 110, "h2d_bytes": 10240,
             "d2h_bytes": 22528}
    run = _run(before=before, after=after, served=5)
    assert queue_wait_ms.read(run) == pytest.approx(5.0)
    assert h2d_kb_per_req.read(run) == pytest.approx(2.0)
    assert d2h_kb_per_req.read(run) == pytest.approx(4.0)
    # A program without these counters: the readers find nothing.
    bare = _run(before={"requests": 0}, after={"requests": 5}, served=5)
    for reader in (queue_wait_ms, h2d_kb_per_req, d2h_kb_per_req):
        assert reader.read(bare) is None
    for reader in TRACE_READERS:
        assert reader.read(bare) is None


RECORDED = "swan-hot-rate.tpu-v5-lite.xplane.pb.gz"


def test_trace_without_program_spans():
    """The trace recorded before the program had spans: the device
    reduction gives what it gave then, every key and value, and the span
    readers find nothing (the device's operations are not walked)."""
    profile = _profile(RECORDED)
    got = tr.reduce_profile(profile)
    assert got["window_s"] == pytest.approx(0.358213888, rel=1e-12)
    assert got["busy_s"] == pytest.approx(0.020923909, rel=1e-12)
    assert got["chips"] == 1
    assert got["module_s"] == pytest.approx(
        {"vm": 0.0, "fused": 0.020851422, "other": 0.000101823}, rel=1e-9)
    assert got["breakdown"]["device_ops"] == [
        [n, pytest.approx(t, rel=1e-9)] for n, t in [
            ("jit__execute(16365502791897209945)/broadcast_select_fusion.10",
             0.001125531),
            ("jit__execute(1200009680054177921)/broadcast_select_fusion.10",
             0.000750355),
            ("jit__execute(7001221656692352585)/fusion.3", 0.000698116),
            ("jit__execute(7001221656692352585)/fusion.2", 0.000698101),
            ("jit__execute(16365502791897209945)/multiply_select_fusion.2",
             0.000519129),
            ("jit__execute(8744114678195035985)/fusion.4", 0.000518274),
            ("jit__execute(8744114678195035985)/fusion.5", 0.000420239),
            ("jit__execute(6129653938138041695)/broadcast_select_fusion.10",
             0.000375585),
            ("jit__execute(1200009680054177921)/multiply_select_fusion.2",
             0.000346087),
            ("jit__execute(8744114678195035985)/fusion.7", 0.000238869)]]
    wait_sleep = "client.wait+client.sleep"
    all3 = "client.submit+client.wait+client.sleep"
    assert got["breakdown"]["idle_gaps"] == [
        [n, pytest.approx(t, rel=1e-9)] for n, t in [
            (wait_sleep, 0.035297336), (wait_sleep, 0.034656915),
            ("client.wait", 0.033115947), (all3, 0.031624948),
            (wait_sleep, 0.030769244), (all3, 0.017472161),
            (wait_sleep, 0.010392116), (wait_sleep, 0.009902452),
            (wait_sleep, 0.007883815), (wait_sleep, 0.005798517)]]
    assert set(got) == {"window_s", "busy_s", "chips", "module_s",
                        "breakdown"}

    reduced = sp.reduce_profile(profile)
    assert reduced == {"window_s": pytest.approx(0.358213888, rel=1e-12),
                       "spans": {}, "worker_busy_s": None, "idle_s": None,
                       "idle_host_s": None}
    for reader in TRACE_READERS:
        assert reader.read(_run(reduced)) is None
