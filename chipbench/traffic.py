"""The one traffic generator: a traffic file plus ``--seed`` -> requests.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only:

``arrivals``
    the arrival kind, a module ``chipbench/arrivals/<kind>.py`` that
    turns the file's parameters into a schedule and drives the window
    (its docstring lists the parameters it reads).
``pool``
    distinct seeded instances (program + memory image) built per kernel;
    requests take them in turn.  ``"fresh"`` builds one per request and
    checks that no program text repeats within the run.
``vary`` (optional)
    kernel name -> {builder argument: [low, high]}: each instance of the
    kernel takes the argument from the evenly spaced quantiles of that
    range, in an order drawn from the seed.  A fresh-program mix uses it
    where the program text depends on the data only through a count (an
    spmm program depends on its number of non-zeros alone).
``check_per_kernel``
    served results per kernel kept, drawn from the seed, for the
    comparison with the reference after the window.

Kernel names resolve through the configuration file's ``kernels``:
``{"family": "patterns" | "nn", "kernel": <builder>, "args": {...}}``,
built by ``repro.core.patterns.PATTERNS`` or ``repro.nn.BLOCK_KERNELS``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_SEED_SPACE = 1 << 62
#: Seeds tried per request before giving up on a unique program.
_FRESH_TRIES = 64


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A generator that depends on the run's seed and on ``salt`` only."""
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


@dataclasses.dataclass(frozen=True)
class Instance:
    kernel: str
    program: tuple
    memory: np.ndarray


@dataclasses.dataclass
class Request:
    index: int
    kernel: str
    instance: Instance
    due: Optional[float] = None      # seconds after the window opens


def build(config: dict, kernel: str, seed: int, **override) -> Instance:
    entry = config["kernels"][kernel]
    args = dict(entry.get("args", {}), **override, seed=int(seed))
    if entry["family"] == "patterns":
        from repro.core.patterns import PATTERNS
        run = PATTERNS[entry["kernel"]](**args)
        program = run.program
    elif entry["family"] == "nn":
        from repro.nn import BLOCK_KERNELS
        run = BLOCK_KERNELS[entry["kernel"]](**args)
        program = run.kernel.program
    else:
        raise ValueError(f"unknown kernel family {entry['family']!r}")
    return Instance(kernel, tuple(program), run.memory)


def apportion(weights: Dict[str, float], total: int) -> Dict[str, int]:
    """Split ``total`` by ``weights`` (largest remainder, ties by order)."""
    names = list(weights)
    wsum = float(sum(weights.values()))
    exact = [weights[n] * total / wsum for n in names]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(names)), key=lambda i: -(exact[i] - counts[i]))
    for i in order[:total - sum(counts)]:
        counts[i] += 1
    return dict(zip(names, counts))


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """The ``n`` quantiles of an exponential distribution of mean
    ``1/rate``, in increasing order."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


class Traffic:
    """The requests of one run: the instances it submits, and the arrival
    kind (:attr:`arrivals`) that schedules them and drives the window."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float):
        from chipbench import spec
        self.config = config
        self.params = traffic
        self.seed = int(seed)
        self.fresh = traffic["pool"] == "fresh"
        kind = spec.arrival_kind(traffic["arrivals"])
        self.kernels: List[str] = kind.kernels(traffic)
        self.arrivals = kind.Arrivals(self, float(seconds))

    # -- instances ---------------------------------------------------------
    def _seeds(self, kernel: str, n: int) -> List[int]:
        k = self.kernels.index(kernel)
        return [int(s) for s in
                rng_for(self.seed, 1, k).integers(0, _SEED_SPACE, n)]

    def _args(self, kernel: str, n: int) -> List[dict]:
        """Per-instance builder arguments from ``vary``."""
        ranges = self.params.get("vary", {}).get(kernel, {})
        out = [{} for _ in range(n)]
        k = self.kernels.index(kernel)
        for j, (arg, (lo, hi)) in enumerate(sorted(ranges.items())):
            q = lo + (hi - lo) * (np.arange(n) + 0.5) / n
            for a, v in zip(out, rng_for(self.seed, 4, k, j).permutation(q)):
                a[arg] = float(v)
        return out

    def instances(self, counts: Dict[str, int]) -> Dict[str, List[Instance]]:
        """Per kernel, the instances its requests take in turn: ``counts``
        new programs each for a fresh pool, else the seeded pool."""
        if self.fresh:
            return self._fresh(counts)
        size = int(self.params["pool"])
        return {k: [build(self.config, k, s, **a) for s, a in
                    zip(self._seeds(k, size), self._args(k, size))]
                for k in self.kernels}

    def _fresh(self, counts: Dict[str, int]) -> Dict[str, List[Instance]]:
        seen, out = set(), {}
        for k, n in counts.items():
            seeds = iter(self._seeds(k, n * _FRESH_TRIES))
            got = []
            for args in self._args(k, n):
                for _ in range(_FRESH_TRIES):
                    inst = build(self.config, k, next(seeds), **args)
                    if inst.program not in seen:
                        break
                else:
                    raise ValueError(f"{k}: no unique program in "
                                     f"{_FRESH_TRIES} seeds; widen 'vary'")
                seen.add(inst.program)
                got.append(inst)
            out[k] = got
        return out

    # -- what set-up must warm ---------------------------------------------
    def programs(self) -> List[Tuple[Instance, int]]:
        """Each distinct program text the run submits, with one instance
        of it and how often the run can submit it (2 for "many")."""
        seen: Dict[tuple, List] = {}
        for inst, n in self.arrivals.submissions():
            if inst.program in seen:
                seen[inst.program][1] = 2
            else:
                seen[inst.program] = [inst, min(n, 2)]
        return [(i, n) for i, n in seen.values()]

    def sampler(self) -> "Reservoir":
        return Reservoir(self.kernels, int(self.params["check_per_kernel"]),
                         rng_for(self.seed, 3))


class Reservoir:
    """Keeps ``size`` uniformly drawn served results per kernel."""

    def __init__(self, kernels: Sequence[str], size: int,
                 rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.seen = {k: 0 for k in kernels}
        self.kept: Dict[str, list] = {k: [] for k in kernels}

    def offer(self, kernel: str, item) -> None:
        self.seen[kernel] += 1
        kept = self.kept[kernel]
        if len(kept) < self.size:
            kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen[kernel]))
            if j < self.size:
                kept[j] = item

    def items(self) -> list:
        return [it for k in self.kept for it in self.kept[k]]
