"""The trace reduction against hand-computed numbers, on plain events
and on a trace recorded on a TPU v5e chip."""
import os

import pytest

from chipbench import reference, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_and_gaps():
    iv = [(5, 8), (0, 2), (1, 3), (8, 9), (20, 25)]
    assert tr.union(iv) == [(0, 3), (5, 9), (20, 25)]
    assert tr.covered(iv) == 3 + 4 + 5
    assert tr.clip(iv, 2, 21) == [(5, 8), (2, 3), (8, 9), (20, 21)]
    assert tr.gaps(iv, 0, 30) == [(3, 5), (9, 20), (25, 30)]


def test_reduce_events_by_hand():
    """Window 100..200 ns; device busy 110-130 (two overlapping ops),
    150-160 and 190-230 (cut at 200): 20 + 10 + 10 = 40 ns busy, idle
    60 %.  Gaps: 100-110 (10), 130-150 (20), 160-190 (30)."""
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)",
                              110, 15),
                             ("%copy.2 = f32[8]{0} copy(f32[8]{0} %b)", 120, 10),
                             ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %c)",
                              150, 10),
                             ("%while.3 = (s32[]) while((s32[]) %t)", 190, 40)]}
    modules = {"/device:TPU:0": [("jit_execute(12)", 110, 20),
                                 ("jit__execute(40)", 150, 10),
                                 ("jit__execute(40)", 190, 40),
                                 ("jit_convert_element_type", 300, 5)]}
    spans = {"bench.window": [(100, 200)],
             "client.submit": [(155, 170)],
             "client.wait": [(100, 200)]}
    got = tr.reduce_events(ops, modules, spans)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["module_s"] == pytest.approx(
        {"vm": 20e-9, "fused": 20e-9, "other": 0.0})
    assert got["breakdown"]["device_ops"] == [
        ["jit_execute(12)/fusion.1", pytest.approx(15e-9)],
        ["jit_execute(12)/copy.2", pytest.approx(10e-9)],
        ["jit__execute(40)/fusion.1", pytest.approx(10e-9)],
        ["jit__execute(40)/while.3", pytest.approx(10e-9)]]
    assert got["breakdown"]["idle_gaps"] == [
        ["client.wait", pytest.approx(30e-9)],
        ["client.wait", pytest.approx(20e-9)],
        ["client.wait", pytest.approx(10e-9)]]
    spans["client.submit"] = [(170, 180)]
    got = tr.reduce_events(ops, modules, spans)
    assert got["breakdown"]["idle_gaps"][0][0] == "client.submit+client.wait"


def test_executable_classes():
    assert tr.executable_class("jit_execute(3)") == "vm"
    assert tr.executable_class("jit__execute(9)") == "fused"
    assert tr.executable_class("jit_convert_element_type") == "other"


def test_access_bytes_by_hand():
    """A 2-D load of 4 x 3 elements whose inner dimension is replicated
    (stride 0) touches 3 distinct words; a 1-D store of 8 halves 8; a
    2 x 4 store with the top dimension's element 3 masked off 6."""
    from repro.core import isa
    F, HF = isa.DType.F, isa.DType.HF
    prog = [isa.vsetdimc(2), isa.vsetdiml(0, 4), isa.vsetdiml(1, 3),
            isa.vsld(F, 1, 10, 0, 1),            # x replicated: 3 words
            isa.vsetdimc(1), isa.vsetdiml(0, 8),
            isa.vsst(HF, 1, 100, 1),             # 8 halves: 16 bytes
            isa.vsetdimc(2), isa.vsetdiml(0, 2), isa.vsetdiml(1, 4),
            isa.vunsetmask(3),                   # drop top element 3
            isa.vsst(F, 1, 200, 1, 2)]           # 2 x 3 words: 24 bytes
    assert reference.access_bytes(prog, [0.0] * 300, 64) == 12 + 16 + 24


RECORDED = os.path.join(DATA, "swan-hot-rate.tpu-v5-lite.xplane.pb.gz")


def test_recorded_tpu_trace():
    """A 0.3 s traced window of ``swan-hot-rate`` recorded on one TPU v5
    lite (192 requests).  The expected numbers come from a sweep-line
    count over the raw events, written apart from this reduction: 4265
    ``XLA Ops`` and 154 ``XLA Modules`` events on ``/device:TPU:0``."""
    import gzip

    from jax.profiler import ProfileData
    with gzip.open(RECORDED, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    got = tr.reduce_profile(profile)
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(0.358213888, rel=1e-12)
    assert got["busy_s"] == pytest.approx(0.020923909, rel=1e-12)
    assert got["module_s"]["fused"] == pytest.approx(0.020851422, rel=1e-9)
    assert got["module_s"]["vm"] == 0.0
    ops = got["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0].startswith("jit__execute(")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = got["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and all("client." in g[0] for g in gaps)
