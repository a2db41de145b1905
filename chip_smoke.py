"""Bring-up smoke run of both systems on one TPU chip.

    python3 chip_smoke.py

One process, no options.  It reads ``jax.devices()`` first and exits
non-zero, printing no result, unless JAX sees a TPU: there is no CPU
fallback.  Then it drives each system once through the entry points a
user calls, at full size:

* ``mve_serving`` — the MVE program server (``MVEProgramServer`` over
  ``MVEScheduler``) at the paper's 8192-lane geometry replays the
  64-request Section-IV stream and the ``repro.nn`` block stream until
  both the VM and the promoted fused tier serve.  Every result must pass
  its own oracle and equal, bit for bit, the stepwise interpreter run on
  the host CPU; every result must come from the ``vm`` or ``fused`` tier
  with no retry, bisection, demotion, oracle serve or breaker opening,
  so the scheduler's self-healing cannot hide a chip fault.
* ``lm_serving`` — qwen2-0.5b at its published widths, random weights
  from ``SEED``: ``ContinuousBatchingEngine`` answers 8 requests, one
  request's final-step logits are checked against ``LM.prefill`` over the
  same tokens, and a ``use_pallas`` prefill must compile the flash
  kernel through Mosaic (``tpu_custom_call``) and agree with the jnp path.

Each phase prints one ``[smoke reading]`` line: host wall-clock seconds,
counts and device memory of this one run, for bring-up only — they are
not benchmark metrics.  The last line of standard output is
``{"ok": true, "device": {...}}`` when every phase passed; any failure
exits non-zero.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# The MVE oracle runs on the host CPU, so its backend must load next to
# whatever platform list the machine pins.
_PLATFORMS = os.environ.get("JAX_PLATFORMS")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

SEED = 0
#: Replays of the MVE streams through a server that promotes repeated
#: programs to the fused tier, after one through a pure-VM server.
MVE_PROMOTING_REPLAYS = 2
LM_SLOTS, LM_MAX_SEQ, LM_NEW_TOKENS = 8, 512, 16
LM_PROMPT_LENS = (32, 128)
#: Bound on ||a - b|| / ||b|| between two bf16 paths' logits.  Two correct
#: paths differ only by bf16 rounding (unit roundoff 2^-8) carried through
#: 24 layers; a wrong position, mask or cache slot moves logits by O(1).
LOGIT_REL_TOL = 5e-2


def require_tpu() -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found {len(devices)} "
                 f"{dev.platform} device(s) ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _reading(phase: str, **values) -> None:
    print(f"[smoke reading] {phase}: {json.dumps(values)}", flush=True)


def _bit_diff(got, want) -> str | None:
    """None when ``got`` and ``want`` hold the same bits, else a note."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.dtype != want.dtype or got.shape != want.shape:
        return f"{got.dtype}{got.shape} != {want.dtype}{want.shape}"
    if got.tobytes() == want.tobytes():
        return None
    bad = np.flatnonzero(got.view(np.uint8).reshape(got.size, -1)
                         != want.view(np.uint8).reshape(want.size, -1)
                         ) // got.itemsize
    first = np.unique(bad)[:3]
    return (f"{len(np.unique(bad))} elements differ, e.g. "
            f"{[(int(i), got.flat[i].item(), want.flat[i].item()) for i in first]}")


# ---------------------------------------------------------------------------
# MVE program serving.
# ---------------------------------------------------------------------------

def mve_serving(oracle_device) -> dict:
    """Serve the pattern and block streams; returns the phase readings."""
    import jax
    import numpy as np

    from benchmarks.serving_bench import _lm_block_stream, request_stream
    from repro.core import MVEConfig, MVEInterpreter, vm
    from repro.launch.serve import MVEProgramServer

    cfg = MVEConfig()
    t0 = time.perf_counter()
    vm.prewarm(cfg)
    prewarm_s = time.perf_counter() - t0

    # Requests, their oracles' expected values and the stepwise runs are
    # all computed on the host CPU: the reference platform.
    oracle_on = functools.partial(jax.default_device, oracle_device)
    t0 = time.perf_counter()
    interp = MVEInterpreter(cfg, compiled=False)
    want = []
    with oracle_on():
        # (name, what to submit, memory image, oracle check)
        stream = ([(n, r.program, r.memory, r.check)
                   for n, r in request_stream(seed=SEED)]
                  + [(n, r.kernel, r.memory, r.check)
                     for n, r in _lm_block_stream(quick=False, copies=2)])
        for name, prog, mem, _ in stream:
            prog = getattr(prog, "program", prog)
            mem_i, st_i = interp.run_stepwise(list(prog), mem)
            want.append((np.asarray(mem_i),
                         {r: np.asarray(v) for r, v in st_i.regs.items()},
                         np.asarray(st_i.tag)))
    oracle_s = time.perf_counter() - t0

    cold = MVEProgramServer(cfg=cfg, promote_after=None)
    hot = MVEProgramServer(cfg=cfg, promote_after=2, max_batch=16)
    walls, tiers, mismatches = [], collections.Counter(), []
    for replay, server in enumerate([cold] + [hot] * MVE_PROMOTING_REPLAYS):
        reqs = [server.submit(prog, mem) for _, prog, mem, _ in stream]
        t0 = time.perf_counter()
        server.run_until_drained()
        walls.append(time.perf_counter() - t0)
        for req, (name, _, _, check), (mem_w, regs_w, tag_w) in zip(
                reqs, stream, want):
            if req.error is not None:
                raise RuntimeError(f"{name}: {req.error!r}")
            res = req.result
            tiers[res.tier] += 1
            with oracle_on():
                check(res.memory, res)
            diffs = [("memory", _bit_diff(res.memory, mem_w)),
                     ("tag", _bit_diff(res.tag, tag_w))]
            diffs += [(f"v{r}", _bit_diff(res.regs[r], w))
                      for r, w in regs_w.items()]
            mismatches += [f"replay {replay} {name} [{res.tier}] {what}: "
                           f"{note}" for what, note in diffs if note]
    if mismatches:
        raise AssertionError("results differ from the CPU stepwise oracle:\n"
                             + "\n".join(mismatches[:40]))

    faults = {}
    for label, server in (("vm_server", cold), ("promoting_server", hot)):
        health = server.health()
        counters = dict(health["counters"],
                        breaker_opens=health["breakers"]["opens"])
        faults.update({f"{label}.{k}": counters[k] for k in (
            "oracle_serves", "demotions", "retries", "bisections",
            "breaker_opens") if counters[k]})
    if faults or set(tiers) != {"vm", "fused"}:
        raise AssertionError(f"tiers {dict(tiers)}, health faults {faults}")
    return {"requests_per_replay": len(stream),
            "replays": 1 + MVE_PROMOTING_REPLAYS, "tiers": dict(tiers),
            "prewarm_compile_s": prewarm_s,
            "replay_wall_s": walls,
            "cpu_oracle_s": oracle_s, "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------
# LM serving.
# ---------------------------------------------------------------------------

def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def lm_serving(cfg) -> dict:
    """Continuous batching + prefill checks for ``cfg``; returns the
    phase readings."""
    import jax
    import numpy as np

    from repro.launch.serve import ContinuousBatchingEngine, Request
    from repro.models.lm import LM

    model = LM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(
        jax.random.PRNGKey(SEED)))
    init_s = time.perf_counter() - t0

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in rng.integers(LM_PROMPT_LENS[0],
                                     LM_PROMPT_LENS[1] + 1, LM_SLOTS)]
    engine = ContinuousBatchingEngine(cfg, params, batch_slots=LM_SLOTS,
                                      max_seq=LM_MAX_SEQ)
    # Keep the logits row of request 0 at each step it takes part in;
    # the last one kept is its final step.
    decode, final, steps = engine._decode, {}, [0]

    def recording_decode(*args):
        logits, cache = decode(*args)
        for slot in engine.grid.active_slots():
            if engine.grid.payload(slot).request.rid == 0:
                final["logits"] = logits[slot, :cfg.vocab_size]
        steps[0] += 1
        return logits, cache

    engine._decode = recording_decode
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=LM_NEW_TOKENS))
    t0 = time.perf_counter()
    engine.step()
    first_step_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    drain_s = time.perf_counter() - t0
    outputs = [len(done[r].output) if r in done else 0
               for r in range(LM_SLOTS)]
    if outputs != [LM_NEW_TOKENS] * LM_SLOTS:
        raise AssertionError(f"new tokens per request: {outputs}")

    # Request 0's final step fed output[-2] at the last position; prefill
    # over prompt + output[:-1] ends at that same position.
    seq = np.concatenate([prompts[0], done[0].output[:-1]]).astype(np.int32)
    batch = {"tokens": seq[None], "positions": np.arange(len(seq),
                                                         dtype=np.int32)[None]}
    t0 = time.perf_counter()
    ref = np.asarray(jax.jit(model.prefill)(params, batch)[0][0,
                     :cfg.vocab_size], np.float32)
    prefill_s = time.perf_counter() - t0
    engine_err = _rel_err(final["logits"], ref)

    pallas = LM(dataclasses.replace(cfg, use_pallas=True))
    t0 = time.perf_counter()
    compiled = jax.jit(pallas.prefill).lower(params, batch).compile()
    pallas_compile_s = time.perf_counter() - t0
    mosaic = "tpu_custom_call" in compiled.as_text()
    pallas_err = _rel_err(compiled(params, batch)[0][0, :cfg.vocab_size],
                          ref)
    readings = {"requests": LM_SLOTS, "new_tokens": sum(outputs),
                "prompt_tokens": int(sum(len(p) for p in prompts)),
                "decode_steps": steps[0], "init_s": init_s,
                "first_step_s": first_step_s, "drain_s": drain_s,
                "prefill_first_call_s": prefill_s,
                "pallas_prefill_compile_s": pallas_compile_s,
                "engine_vs_prefill_rel_err": engine_err,
                "pallas_vs_jnp_prefill_rel_err": pallas_err,
                "pallas_prefill_tpu_custom_call": mosaic,
                "peak_bytes_in_use": _peak_bytes()}
    if not (engine_err <= LOGIT_REL_TOL and pallas_err <= LOGIT_REL_TOL
            and mosaic):
        raise AssertionError(f"LM checks failed: {readings}")
    return readings


def main() -> int:
    device = require_tpu()
    import jax

    from repro.configs.qwen2_0_5b import CONFIG
    from repro.core import vm
    print(f"[smoke] device {device}; compile cache at "
          f"{vm.enable_disk_cache()}", flush=True)
    phases = {"mve_serving": lambda: mve_serving(jax.devices("cpu")[0]),
              "lm_serving": lambda: lm_serving(CONFIG)}
    failed = []
    for name, phase in phases.items():
        try:
            _reading(name, device_kind=device["kind"], **phase())
        except Exception:
            traceback.print_exc()
            print(f"[smoke] phase {name} FAILED", flush=True)
            failed.append(name)
    if failed:
        print(f"[smoke] failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
