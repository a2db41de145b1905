"""Mean host time a client spends inside ``MVEScheduler.submit`` per
request in the window (the front door: admission plus lowering by
``engine.compile_program``), from the benchmark's own clock around the
call."""


def read(run):
    spent = [r.accepted - r.submitted for r in run.window.records
             if r.submitted is not None and r.accepted is not None]
    return sum(spent) / len(spent) * 1e3 if spent else None
