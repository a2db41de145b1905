"""``isa.Instr`` computes its hash once per object and keeps it.

Every program-keyed cache (the engine LRU, the scheduler's heat, promotion
and quarantine maps, its grouping buckets) hashes whole programs; these
tests pin that the kept hash is the value the dataclass would generate,
that it is computed once per object (``cache_info().instr_hashes``), and
that it never leaves the object by pickling or copying.
"""
import copy
import dataclasses
import itertools
import pickle

import pytest

from repro.core import MVEConfig, cache_info, compile_program, engine, isa
from repro.core.isa import DType
from repro.core.patterns import PATTERNS
from repro.runtime.scheduler import MVEScheduler

CFG = MVEConfig()
_IMM = itertools.count(1000)   # fresh immediates: never hashed before


def _generated_hash(instr: isa.Instr) -> int:
    """What the dataclass-generated ``__hash__`` returns."""
    return hash(tuple(getattr(instr, f.name)
                      for f in dataclasses.fields(instr)))


def _program():
    """A small program of instructions no earlier test has hashed."""
    k = next(_IMM)
    return [isa.vsetdimc(1), isa.vsetdiml(0, 8),
            isa.vsetdup(DType.DW, 0, k), isa.vsetdup(DType.DW, 1, k + 1),
            isa.Instr(isa.Op.ADD, DType.DW, vd=2, vs1=0, vs2=1),
            isa.Instr(isa.Op.SST, DType.DW, vs1=2, base=0, modes=(1,))]


_BUILDERS = {
    "config": lambda: isa.vsetdiml(1, 16),
    "dup": lambda: isa.vsetdup(DType.F, 3, 7),
    "strided_load": lambda: isa.Instr(isa.Op.SLD, DType.HF, vd=1, base=64,
                                      modes=(1, 2, 0)),
    "predicated_add": lambda: isa.Instr(isa.Op.ADD, DType.QW, vd=2, vs1=0,
                                        vs2=1, predicated=True),
    "scalar": lambda: isa.Instr(isa.Op.SCALAR, scalar_count=5),
}


@pytest.mark.parametrize("kind", sorted(_BUILDERS))
def test_equal_instrs_hash_equal_to_the_generated_hash(kind):
    a, b = _BUILDERS[kind](), _BUILDERS[kind]()
    assert a is not b and a == b
    assert hash(a) == hash(b) == _generated_hash(a)
    assert vars(a)["_hash"] == vars(b)["_hash"] == hash(a)   # kept


def test_replace_hashes_its_new_fields():
    a = isa.Instr(isa.Op.ADD, DType.DW, vd=2, vs1=0, vs2=1)
    h = hash(a)
    b = dataclasses.replace(a, vd=5)
    assert "_hash" not in vars(b)
    assert hash(b) == _generated_hash(b) != h
    assert hash(dataclasses.replace(b, vd=2)) == h


@pytest.mark.parametrize("copy_out", [
    lambda i: pickle.loads(pickle.dumps(i)),
    copy.copy,
    copy.deepcopy,
    dataclasses.replace,
], ids=["pickle", "copy", "deepcopy", "replace"])
def test_copies_carry_no_kept_hash(copy_out):
    a = isa.Instr(isa.Op.SLD, DType.F, vd=1, base=next(_IMM), modes=(1, 2))
    h = hash(a)
    assert "_hash" in vars(a)
    b = copy_out(a)
    assert "_hash" not in vars(b)
    assert "_hash" not in a.__getstate__()
    assert b == a and hash(b) == h


@pytest.mark.parametrize("use", [
    hash,
    lambda p: compile_program(p, CFG),
    lambda p: compile_program(p, CFG, mode="fused"),
], ids=["hash", "compile_vm", "compile_fused"])
def test_instr_hashes_counts_one_computation_per_object(use):
    prog = tuple(_program())
    before = cache_info().instr_hashes
    for _ in range(5):
        use(prog)                # first use computes, the rest hit
    assert cache_info().instr_hashes - before == len(prog)
    assert all("_hash" in vars(i) for i in prog)


def test_program_of_fresh_equal_instrs_hits_the_lru():
    prog = _program()
    cp = compile_program(prog, CFG)
    info = cache_info()
    fresh = [dataclasses.replace(i) for i in prog]
    assert all(a is not b for a, b in zip(prog, fresh))
    assert compile_program(fresh, CFG) is cp
    after = cache_info()
    assert after.program_hits == info.program_hits + 1
    assert after.program_misses == info.program_misses
    assert after.instr_hashes - info.instr_hashes == len(fresh)


def test_scheduler_serves_a_compiled_program_without_new_hashes():
    """Submitting, grouping and promoting a program already seen hashes
    no instruction again: nothing on the hot path rebuilds its Instrs."""
    engine.clear_cache()
    run = PATTERNS["daxpy"]()
    sched = MVEScheduler(CFG, promote_after=2)
    for _ in range(3):
        sched.submit(run.program, run.memory)
    sched.drain()                                 # promotes to fused
    assert sched.stats.promotions == 1
    before = cache_info().instr_hashes
    tickets = [sched.submit(run.program, run.memory) for _ in range(4)]
    assert cache_info().instr_hashes == before    # submit()
    sched.drain()                                 # group, admit, promotion
    assert all(t.result() is not None for t in tickets)
    assert cache_info().instr_hashes == before
