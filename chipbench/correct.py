"""The comparison that decides ``correct``.

Each kept result (drawn from the seed among those the window served) is
compared with :mod:`chipbench.reference` run on the request's own program
and input image: the memory image, every register the reference wrote
and the Tag latch, bit for bit.  The ISA's semantics are exact, so an
exact comparison is the right one and its limit is 0.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import reference

#: Each number compared, with its limit (the run is correct when every
#: number is at most its limit).  PERF.md gives the readings behind them.
LIMITS = {"mismatched_results": 0, "failed_requests": 0,
          "unchecked_kernels": 0}


def _same(got, want: np.ndarray) -> bool:
    got = np.asarray(got)
    return (got.dtype == want.dtype and got.shape == want.shape
            and got.tobytes() == want.tobytes())


def differs(res, want) -> List[str]:
    """What of a served result differs from the reference's answer."""
    mem, regs, tag = want
    bad = [] if _same(res.memory, mem) else ["memory"]
    for r, v in regs.items():
        if r not in res.regs or not _same(res.regs[r], v):
            bad.append(f"v{r}")
    if not _same(np.asarray(res.tag, dtype=bool), tag):
        bad.append("tag")
    return bad


class _Answer:
    """A reference answer standing in for a served one (the control)."""

    def __init__(self, mem, regs, tag):
        self.memory, self.regs, self.tag = mem, regs, tag


def check(sample, lanes: int, control: bool = False
          ) -> Tuple[int, List[str]]:
    """(results that differ, notes) over ``sample``.  With ``control``
    the bfloat16 reference stands in for the served results."""
    bad, notes = 0, []
    for req, res in sample:
        inst = req.instance
        want = reference.run(inst.program, inst.memory, lanes)
        if control:
            res = _Answer(*reference.run(inst.program, inst.memory, lanes,
                                         bf16=True))
        diff = differs(res, want)
        if diff:
            bad += 1
            if len(notes) < 8:
                notes.append(f"{req.kernel} request {req.index}: "
                             f"{', '.join(diff)} differ")
    return bad, notes


def verdict(numbers: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in LIMITS.items()}
    ok = all(v["value"] <= v["limit"] for v in checks.values())
    return ok, checks
