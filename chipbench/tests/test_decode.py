"""The ``decode`` arrival kind and its readers on the CPU, at the
``qwen2-0.5b-smoke`` widths: a short window through the scheduler, the
bit-exact tile sample, the model-level check and its bfloat16 control;
and the per-kind device-time reduction on hand-made events."""
import copy
import json
import os

import numpy as np
import pytest

from chipbench import correct, decode_trace, drive, spec
from chipbench import trace_reduce as tr
from chipbench.arrivals import decode
from chipbench.metrics import (attn_device_us_per_step, attn_roofline,
                               gemm_device_us_per_step, gemm_roofline,
                               stage_wait_ms)
from chipbench.run import Run, read

CELL = "qwen2-decode-4k"


def test_reduce_events_by_hand():
    """Window 100..200 ns: attention modules 110-130 and 190-230 (cut at
    200), a GEMV module 140-150, a Swan fused module and the VM ignored."""
    modules = [("jit__execute.dec_attn(7)", 110, 130),
               ("jit__execute.dec_attn(8)", 190, 230),
               ("jit__execute.dec_gemv_d(9)", 140, 150),
               ("jit__execute(40)", 150, 160), ("jit_execute(3)", 160, 170),
               ("jit__execute.dec_norm(2)", 250, 260)]
    got = decode_trace.reduce_events(modules, (100, 200))
    assert got == pytest.approx({"attn": 30e-9, "gemv_d": 10e-9,
                                 "norm": 0.0})


def test_named_executables_stay_fused():
    """A kind's executable keeps ``trace_reduce``'s fused class."""
    assert tr.executable_class("jit__execute.dec_attn(12)") == "fused"
    assert tr.executable_class("jit__execute.dec_gemv_ff(3)") == "fused"
    assert tr.executable_class("jit_execute(3)") == "vm"


def _smoke_cell():
    """The benchmark's cell at the smoke widths: 2 layers, 2 sessions of
    12 positions, splits of 8, fused batches of up to 2."""
    cell = spec.cell(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(smoke=True, hidden_size=56, intermediate_size=96,
                        num_attention_heads=7, num_key_value_heads=1,
                        rms_norm_eps=1e-5, rope_theta=10000.0,
                        num_hidden_layers=2)
    cfg["decode"] = {"split": 8, "max_splits": 4}
    cfg["server"]["max_batch"] = 2
    traffic = dict(cell.traffic, sessions=2, history=12,
                   check_per_kernel=1)
    return spec.Cell(CELL, 1, cfg, traffic, cell.end_to_end, cell.per_layer)


@pytest.fixture(scope="module")
def smoke_run():
    cell = _smoke_cell()
    s = drive.setup(cell, 2**31 + 12345, 2.0)
    window = s.window(drive.Spans(False))
    s.sched.close(drain=False)
    return cell, s, window


def test_decode_window_is_correct(smoke_run):
    cell, s, window = smoke_run
    recs = window.records
    assert recs and all(r.error is None and r.seen is not None for r in recs)
    assert window.decode["steps_checked"] == 4
    assert window.decode["relative_error"] < decode.MODEL_LIMIT
    assert window.decode["quant_step_error"] < decode.QUANT_STEP_LIMIT
    assert window.decode["scale_error"] < decode.SCALE_LIMIT
    mismatched, notes = correct.check(window.sample, s.cfg.lanes)
    assert mismatched == 0, notes
    assert {req.kernel for req, _ in window.sample} == set(s.traffic.kernels)
    # the control: the tiles' float32 results rounded to bfloat16 differ
    assert correct.check(window.sample, s.cfg.lanes, control=True)[0] > 0


def test_model_check_rejects_bf16_control(smoke_run):
    """The reference with its norms and attention in bfloat16, in the
    program's place on the same sampled steps, fails ``MODEL_LIMIT`` and
    ``QUANT_STEP_LIMIT``."""
    cell, s, window = smoke_run
    arr = s.traffic.arrivals
    program = arr.model_check(*arr.served_steps)
    control = arr.model_check(*arr.served_steps, "bf16")
    assert program["steps_checked"] == control["steps_checked"] == 4
    assert (program["relative_error"] < decode.MODEL_LIMIT
            < control["relative_error"])
    assert (program["quant_step_error"] < decode.QUANT_STEP_LIMIT
            < control["quant_step_error"])


def test_model_check_rejects_bf16_scales(smoke_run):
    """The reference with only its per-token activation scales in
    bfloat16, in the program's place, computes on from those scales, so
    every other reading passes: ``SCALE_LIMIT`` alone rejects it."""
    cell, s, window = smoke_run
    arr = s.traffic.arrivals
    program = arr.model_check(*arr.served_steps)
    control = arr.model_check(*arr.served_steps, "bf16_scales")
    assert control["steps_checked"] == 4
    assert control["relative_error"] < decode.MODEL_LIMIT
    assert control["quant_step_error"] < decode.QUANT_STEP_LIMIT
    assert program["scale_error"] < decode.SCALE_LIMIT < control[
        "scale_error"]


def test_failed_model_check_fails_correct(smoke_run):
    """A sampled step over a limit gets an error, which ``correct``'s
    ``failed_requests`` counts: here a served scale 1 % off."""
    cell, s, window = smoke_run
    arr = s.traffic.arrivals
    sessions, steps = arr.served_steps
    sess, i = next((s_, idx[0]) for s_, idx in
                   arr.checked_steps(steps).items() if idx)
    rec, out = steps[sess][i]
    q, scale = out.quant[0]["x"]
    saved = rec.error
    out.quant[0]["x"] = (q, scale * np.float32(1.01))
    try:
        got = arr.model_check(sessions, steps)
        assert got["scale_error"] > decode.SCALE_LIMIT
        assert rec.error is not None and "scale_error" in rec.error
    finally:
        out.quant[0]["x"] = (q, scale)
        rec.error = saved


def test_readers(smoke_run):
    cell, s, window = smoke_run
    run = Run(window, s.traffic, s.cfg.lanes, spec.peaks("TPU v5 lite"),
              setup_s=1.0)
    got = read(cell.end_to_end, run)
    assert set(got) == {"req_per_s", "setup_s"}
    assert got["req_per_s"]["value"] > 0
    got = read(cell.per_layer, run)       # untraced: counters only
    assert set(got) == {"stage_wait_ms", "reqs_per_dispatch.dec",
                        "h2d_kb_per_req.dec", "d2h_kb_per_req.dec",
                        "xla_compiles.dec"}
    assert got["stage_wait_ms"]["value"] > 0
    # a traced run's reduction, made up: every kind ran 1 ms
    run.trace = {"module_s": {}, "busy_s": 0.0, "window_s": 1.0}
    run.decode_kinds = {k: 1e-3 for k in window.decode["served_fused"]}
    for reader in (attn_roofline, gemm_roofline):
        assert 0 < reader.read(run) < 100
    steps = len(run.served())
    assert attn_device_us_per_step.read(run) == pytest.approx(1e3 / steps)
    assert gemm_device_us_per_step.read(run) == pytest.approx(2e3 / steps)
    assert stage_wait_ms.read(run) == got["stage_wait_ms"]["value"]


def test_readers_silent_without_decode(smoke_run):
    """On another cell's window, or a parent without the runner's
    counters, the new readers find nothing and report nothing."""
    cell, s, window = smoke_run
    bare = drive.Window(window.records, window.start, window.close,
                        window.end, window.stats_before, window.stats_after,
                        0, [])
    run = Run(bare, s.traffic, s.cfg.lanes, spec.peaks("TPU v5 lite"),
              trace={"module_s": {}}, setup_s=1.0)
    run.decode_kinds = {}
    for reader in (attn_roofline, gemm_roofline, stage_wait_ms,
                   attn_device_us_per_step, gemm_device_us_per_step):
        assert reader.read(run) is None


def test_benchmark_entries():
    """The cell is in BENCHMARK.json as the harness reads it."""
    cell = spec.cell(CELL)
    assert cell.chips == 1 and cell.traffic["arrivals"] == "decode"
    assert [m["name"] for m in cell.end_to_end] == ["req_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"attn_roofline", "gemm_roofline", "stage_wait_ms",
            "device_idle_share.dec", "reqs_per_dispatch.dec",
            "h2d_kb_per_req.dec", "d2h_kb_per_req.dec",
            "idle_host_share.dec", "worker_busy_share.dec",
            "host_us_per_dispatch.dec", "xla_compiles.dec"} <= names
    spec.readers(cell.per_layer + cell.end_to_end)
    path = os.path.join(spec.BENCH_DIR, "configs", "qwen2-0.5b-mve8k.json")
    with open(path) as f:
        decode.model_config(json.load(f))     # widths as registered
