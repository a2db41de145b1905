"""``correct`` comes out true on a sound run and false when the timed
path is broken underneath, or when the control stands in for it.

Each run drives the whole harness (``run.run_cell``) past its look for
a chip, on the CPU, over a small closed-loop mix whose rounds fill
batches, at the machine's real 8192 lanes.
"""
import argparse
import dataclasses

import numpy as np
import pytest

from chipbench import correct, reference, run, spec
from chipbench.traffic import Traffic

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _cell():
    cell = spec.cell("swan-fresh-batch")
    config = dict(cell.config, server=dict(cell.config["server"],
                                           max_batch=4))
    traffic = {"arrivals": "closed", "round": {"daxpy": 8, "memcpy": 4},
               "pool": 4, "check_per_kernel": 12}
    return dataclasses.replace(cell, config=config, traffic=traffic)


def _run(seed=2**31 + 9):
    cell = _cell()
    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=1.0,
                              trace=0)
    return run.run_cell(cell, args, DEVICE, spec.peaks(DEVICE["kind"]))


def _plant(monkeypatch, fault):
    """Break what the executors hand back, where they produce it."""
    from repro.core.engine import CompiledProgram as CP
    run_async, run_batch_async = CP.run_async, CP.run_batch_async
    finalize_run, finalize_batch = CP.finalize_run, CP.finalize_batch

    def one(self, memory):
        return run_async(self, memory), np.asarray(memory, np.float32)

    def many(self, memories):
        return run_batch_async(self, memories), np.asarray(memories,
                                                           np.float32)

    def fin_one(self, pending):
        pending, given = pending
        mem, state = finalize_run(self, pending)
        mem = np.array(mem)
        if fault == "state_unchanged":
            mem = given.copy()
        elif fault == "answer_altered":
            mem[0] += 1.0
        return mem, state

    def fin_many(self, pending):
        pending, given = pending
        mem, regs, tag = finalize_batch(self, pending)
        mem = np.array(mem)
        if fault == "state_unchanged":
            mem = given.copy()
        elif fault == "answer_altered":
            mem[:, 0] += 1.0
        elif fault == "half_batch_left_out":
            half = mem.shape[0] // 2
            mem[half:] = mem[:mem.shape[0] - half]
        return mem, regs, tag

    monkeypatch.setattr(CP, "run_async", one)
    monkeypatch.setattr(CP, "run_batch_async", many)
    monkeypatch.setattr(CP, "finalize_run", fin_one)
    monkeypatch.setattr(CP, "finalize_batch", fin_many)


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 12
    assert list(result)[-1] == "checks"
    for name, c in result["checks"].items():
        assert c == {"value": 0, "limit": correct.LIMITS[name]}


@pytest.mark.parametrize("fault", ["state_unchanged",
                                   "half_batch_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    result = _run()
    assert not result["correct"]
    assert result["checks"]["mismatched_results"]["value"] > 0


def test_control_is_not_correct():
    """The bfloat16 reference in the program's place fails the
    comparison on every float32 request of the sample."""
    cell = _cell()
    traffic = Traffic(cell.config, cell.traffic, 17, 1.0)
    sample = [(r, None) for r in traffic.arrivals.round(0)]
    bad, notes = correct.check(sample, 8192, control=True)
    assert bad == sum(1 for r, _ in sample if r.kernel == "daxpy") == 8
    assert all("memory" in n for n in notes)


def test_reference_matches_the_isa_oracle():
    """The reference agrees bit for bit with the repository's stepwise
    interpreter on one kernel of each data type in the served mixes."""
    from repro.core import MVEConfig, MVEInterpreter
    from chipbench.traffic import build
    interp = MVEInterpreter(MVEConfig(), compiled=False)
    swan = spec.cell("swan-hot-rate").config
    for name in ("alpha_blend", "xor_cipher", "reduction", "spmm", "fir",
                 "gemm"):
        inst = build(swan, name, 2**31 + 1)
        mem, regs, tag = reference.run(inst.program, inst.memory, 8192)
        want_mem, state = interp.run_stepwise(list(inst.program),
                                              inst.memory)

        class Served:
            memory = want_mem
            regs = state.regs
            tag = state.tag
        assert correct.differs(Served, (mem, regs, tag)) == [], name


def test_fresh_window_closes_when_its_rounds_are_used(capsys):
    """A fresh-program window that has used every round built ahead
    closes after the last of them, and what it served is still checked."""
    cell = spec.cell("swan-fresh-batch")
    cell = dataclasses.replace(
        cell, traffic=dict(cell.traffic, rounds_per_s=1 / 30,
                           round={"spmm": 2, "fir": 2}))
    args = argparse.Namespace(workload=cell.name, seed=2**31 + 5,
                              seconds=30.0, trace=0)
    result = run.run_cell(cell, args, DEVICE, spec.peaks(DEVICE["kind"]))
    assert result["correct"], result["checks"]
    assert result["attempted"] == 4 and result["failed"] == 0
    assert "all 1 rounds built were used" in capsys.readouterr().err
    assert result["metrics"]["req_per_s"]["value"] > 0
