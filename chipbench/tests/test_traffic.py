"""The traffic generator and the end-to-end arithmetic, at tiny sizes."""
import subprocess
import sys

import numpy as np
import pytest

from chipbench import drive, run, spec
from chipbench.traffic import Traffic, apportion, poisson_gaps


def _small(cell_name, **params):
    cell = spec.cell(cell_name)
    return cell.config, dict(cell.traffic, **params)


def _key(traffic):
    return [(r.kernel, r.due, r.instance.program, r.instance.memory.tobytes())
            for r in traffic.arrivals.requests]


@pytest.mark.parametrize("pool", [2, "fresh"])
def test_open_traffic_is_a_function_of_the_seed(pool):
    config, params = _small("swan-hot-rate", rate_per_s=20.0, pool=pool)
    if pool == "fresh":
        params.update(mix={"spmm": 1, "fir": 1},
                      vary={"spmm": {"density": [0.1, 0.4]}})
    a = Traffic(config, params, 2**31 + 11, 1.0)
    b = Traffic(config, params, 2**31 + 11, 1.0)
    c = Traffic(config, params, 2**31 + 12, 1.0)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # Every seed offers the same arrivals and kernel counts; the seed
    # orders the kernels.
    due = lambda t: [r.due for r in t.arrivals.requests]
    counts = lambda t: sorted(r.kernel for r in t.arrivals.requests)
    assert due(a) == due(c)
    assert counts(a) == counts(c)
    assert len(a.arrivals.requests) == 20
    assert set(np.diff(due(a)).round(9)) <= set(
        poisson_gaps(20, 20.0).round(9))


def test_closed_traffic_is_a_function_of_the_seed():
    config, params = _small("swan-fresh-batch", pool=2)
    a = Traffic(config, params, 5, 1.0)
    b = Traffic(config, params, 5, 1.0)
    ra, rb = a.arrivals.round(3), b.arrivals.round(3)
    assert [r.kernel for r in ra] == list(
        np.repeat(list(params["round"]), list(params["round"].values())))
    assert [r.instance.memory.tobytes() for r in ra] == \
        [r.instance.memory.tobytes() for r in rb]


def test_fresh_traffic_never_repeats_a_program():
    config, params = _small("swan-fresh-batch", rounds_per_s=4)
    t = Traffic(config, params, 2**31 + 3, 2.0)
    programs = [r.instance.program for k in range(8)
                for r in t.arrivals.round(k)]
    assert len(programs) == 8 * 16
    assert len(set(programs)) == len(programs)
    assert all(n == 1 for _, n in t.programs())
    with pytest.raises(IndexError):
        t.arrivals.round(8)


def test_apportion_and_gaps():
    assert apportion({"a": 7, "b": 6, "c": 2}, 10) == {"a": 5, "b": 4, "c": 1}
    g = poisson_gaps(1000, 50.0)
    assert abs(g.mean() - 1 / 50.0) < 1e-3
    assert np.all(np.diff(g) > 0)


def test_latency_counts_from_the_due_time():
    """A request due at 0 that the server only saw submitted at 0.9 s
    (a stall) and answered at 1.0 s has a latency of 1000 ms."""
    recs = []
    for i in range(20):
        due = float(i)
        # request 19 is submitted 0.9 s late and answered 0.1 s after
        sub = due + (0.9 if i == 19 else 0.0)
        recs.append(drive.Record(request=None, due=due, submitted=sub,
                                 accepted=sub, seen=sub + 0.1))
    w = drive.Window(recs, 0.0, 20.0, 20.0, {}, {}, 0, [])
    one = run.Run(w, None, 8192, {}, setup_s=1.5)
    m = run.read(spec.cell("swan-hot-rate").end_to_end, one)
    assert m["latency_p50_ms"]["value"] == pytest.approx(100.0)
    assert m["setup_s"] == {"value": 1.5, "unit": "s"}
    # p95 of nineteen 100 ms and one 1000 ms (numpy's linear rule)
    tail = spec.reader("tail_p95_ms")(one)
    assert tail == pytest.approx(np.percentile([100.0] * 19 + [1000.0], 95))
    m = run.read(spec.cell("swan-fresh-batch").end_to_end, one)
    assert m["req_per_s"]["value"] == 1.0


def test_run_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "swan-hot-rate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_every_cell_and_metric_resolves():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        spec.readers(cell.end_to_end + cell.per_layer)
        kind = spec.arrival_kind(cell.traffic["arrivals"])
        for k in kind.kernels(cell.traffic):
            assert k in cell.config["kernels"]
    with pytest.raises(spec.SpecError):
        spec.arrival_kind("../run")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
