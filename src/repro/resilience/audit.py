"""Sampled integrity audit of served results.

A bit-flipped SRAM cell does not raise — it *silently* serves a wrong
answer.  The only defense is re-computation on an independent path and a
bit-exact compare, which is exactly the repo's conformance contract
(``tests/test_conformance.py``: interp == fused == VM, bit for bit).
:class:`ResultAuditor` packages that contract as a runtime component the
scheduler samples per served request: it re-executes on the *other*
executor (a VM-served result is checked against the fused engine and
vice versa).  That is cheap (one extra warm dispatch) and catches any
single-executor corruption, because the two executors share no datapath
code past the program walk.  Where there is no other executor (an
oracle-served result, or a program whose other mode falls back to the
served one) the reference is the stepwise interpreter, the semantic
ground truth.

On mismatch the auditor returns the reference payload — the scheduler
serves *that*, counts ``audit_corrected``, and records a breaker failure
against the corrupted executor tier so repeat corruption demotes it.

Sampling is deterministic per ``(seed, rid)``: the same chaos replay
audits the same requests, independent of retry interleaving.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import numpy as np

from ..core.tracing import to_host

AuditReference = Tuple[np.ndarray, Dict[int, np.ndarray], np.ndarray]


class ResultAuditor:
    """Audits served results at ``rate``; ``oracle(cfg)`` gives the
    stepwise interpreter of a machine config (the scheduler's own)."""

    def __init__(self, oracle, rate: float = 1.0, seed: int = 0,
                 injector=None):
        self.oracle = oracle
        self.rate = float(rate)
        self.seed = seed
        self.injector = injector        # suspended during reference runs

    def should_audit(self, rid: int) -> bool:
        if self.rate <= 0.0:
            return False
        if self.rate >= 1.0:
            return True
        return np.random.default_rng((self.seed, rid)).random() < self.rate

    # -- the check ---------------------------------------------------------
    def check(self, program, memory_in, cfg, served_memory, served_tag,
              served_mode: str) -> Optional[AuditReference]:
        """Bit-compare a served result against an independent
        re-execution; returns ``None`` when it verifies, else the
        reference ``(memory, regs, tag)`` to serve instead."""
        program = list(program)
        with self.injector.suspended() if self.injector is not None \
                else contextlib.nullcontext():
            state = None
            if served_mode in ("vm", "fused"):
                from ..core.engine import compile_program
                other = "fused" if served_mode == "vm" else "vm"
                cp = compile_program(program, cfg, mode=other)
                if cp.mode != served_mode:  # no silent same-path "audit"
                    mem, state = cp.run(memory_in)
            if state is None:
                # an oracle-served result, or one whose other mode fell
                # back to the served one: stepwise ground truth
                mem, state = self.oracle(cfg).run_stepwise(program,
                                                           memory_in)
        mem, tag = np.asarray(mem), np.asarray(state.tag)
        if np.array_equal(mem, to_host(served_memory)) and \
                np.array_equal(tag, to_host(served_tag)):
            return None
        return mem, {r: np.asarray(v) for r, v in state.regs.items()}, tag
