"""Decode sessions in a closed loop: each session's client thread runs one
decode step after another, a step starting when the previous step's final
hidden state is in host memory.  A request is one decode step of one
session (one token through every served layer and the final norm), run by
the program's decode-step runner (``repro.nn.qwen2.Qwen2Decoder``), which
submits each stage's tiles through ``MVEScheduler.submit``.  A session
starts no step after the window's seconds, so the window is whole steps
and closes when the last of them ends.

Parameters of the traffic file:

``sessions``
    concurrent sessions, one client thread each.
``history``
    KV positions each session holds at the window's opening, prefilled at
    set-up with seeded values; each step appends one.
``check_per_kernel``
    served tiles kept per tile kind for ``correct``'s bit-exact comparison
    with ``chipbench/reference.py``.
``check_steps_per_session``
    whole steps per session compared after the window with
    ``chipbench/qwen2_reference.py`` (``Arrivals.model_check``); a step
    over :data:`MODEL_LIMIT`, :data:`QUANT_STEP_LIMIT` or
    :data:`SCALE_LIMIT` gets an error, so ``failed_requests`` fails
    ``correct``.

The configuration file's ``model`` block names the registered
architecture (``"smoke": true`` its CPU-test preset) and its widths, its
``decode`` block the attention split, and its ``kernels`` the tile kinds
(``repro.nn.qwen2.tiles`` builds them from the widths).
Weights (``qwen2_reference.init_params``, handed to the runner),
prefilled caches and each step's input hidden state come from the seed,
on the benchmark's side.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import drive
from chipbench import qwen2_reference as ref
from chipbench.traffic import Instance, Request, rng_for
from repro.configs import get_config
from repro.nn import qwen2

#: Largest relative L2 error (``qwen2_reference.compare``: each layer's
#: output, appended keys and values, the final norm) of a checked step,
#: the reference forced with the step's own int8 activations.
MODEL_LIMIT = 1e-4
#: Largest ``quant_step_error`` of those activations: how far past half an
#: int8 step from the reference's own value an element lies, in steps.
QUANT_STEP_LIMIT = 1e-2
#: Largest ``scale_error``: the relative difference of a served per-token
#: scale from the reference's float32 one.  The reference computes on
#: from the served scales, so only this limit sees a scale's error.
#: PERF.md gives the readings behind each limit: the program's largest
#: and its control's smallest.
SCALE_LIMIT = 1e-5

#: The controls :meth:`Arrivals.model_check` can put in the program's
#: place: the reference with its norms and attention in bfloat16, or with
#: only its activation scales in bfloat16.
CONTROLS = {"bf16": {"bf16": True}, "bf16_scales": {"bf16_scales": True}}

#: Widths the configuration states, as ``repro.configs`` names them.
_WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}


def kernels(params: dict) -> List[str]:
    return list(qwen2.KINDS)


def model_config(config: dict):
    """The registered architecture, checked against the widths the
    configuration file states."""
    m = config["model"]
    cfg = get_config(m["registered"], reduced=bool(m.get("smoke", False)))
    for key, attr in _WIDTHS.items():
        if float(getattr(cfg, attr)) != float(m[key]):
            raise ValueError(f"{m['registered']}: {attr} is "
                             f"{getattr(cfg, attr)}, the configuration "
                             f"states {key} {m[key]}")
    return cfg


class Arrivals:
    def __init__(self, traffic, seconds: float):
        self.traffic = traffic
        self.seconds = seconds
        p, config = traffic.params, traffic.config
        seed = traffic.seed
        dec = config["decode"]
        self.sh = qwen2.Shapes.of(model_config(config), dec["split"],
                                  dec["max_splits"])
        self.layers = int(config["model"]["num_hidden_layers"])
        self.sessions = int(p["sessions"])
        self.history = int(p["history"])
        self.dims = ref.dims_of(config["model"])
        self.params = ref.init_params(self.dims, self.layers,
                                      int(rng_for(seed, 5).integers(2**62)))
        shape = (self.layers, self.dims["kv_heads"], self.history,
                 self.dims["head_dim"])
        self.prefill = [
            (rng_for(seed, 6, s, 0).standard_normal(shape, np.float32),
             rng_for(seed, 6, s, 1).standard_normal(shape, np.float32))
            for s in range(self.sessions)]
        images = qwen2.example_images(self.sh,
                                      int(rng_for(seed, 9).integers(2**62)))
        tiles = qwen2.tiles(self.sh)
        self.examples = {k: Instance(k, tiles[k].program, images[k])
                         for k in qwen2.KINDS}
        self.served_steps = None    # (sessions, [(record, output)] each)

    def submissions(self):
        return [(inst, 2) for inst in self.examples.values()]

    def hidden(self, session: int, step: int) -> np.ndarray:
        """The seeded input hidden state of a session's step."""
        return rng_for(self.traffic.seed, 7, session, step).standard_normal(
            self.dims["d"], np.float32)

    def window(self, sched, counter, spans, on_start=None) -> drive.Window:
        sampler = self.traffic.sampler()
        lock = threading.Lock()
        served = {k: 0 for k in qwen2.KINDS}
        index = [0]

        def on_tile(kind, program, image, result):
            # A session rewrites its GEMV images each step: a kept tile
            # keeps a copy of the image it was served with.
            with lock:
                if result.tier == "fused":
                    served[kind] += 1
                index[0] += 1
                item = (Request(index[0], kind,
                                Instance(kind, program, image)), result)
                sampler.offer(kind, item)
                kept = sampler.kept[kind]
                for i, it in enumerate(kept):
                    if it is item:
                        kept[i] = (Request(index[0], kind, Instance(
                            kind, program, image.copy())), result)

        dec = qwen2.Qwen2Decoder(self.sh, self.params, sched,
                                 on_tile=on_tile)
        sessions = []
        for k, v in self.prefill:
            sess = dec.new_session()
            sess.k[:, :, :self.history] = k
            sess.v[:, :, :self.history] = v
            sess.length = self.history
            sessions.append(sess)
        steps: List[List] = [[] for _ in sessions]   # (record, output)
        stats_before = drive.scheduler_stats(sched)
        decode_before = dict(vars(dec.stats))
        if on_start is not None:
            on_start()
        counter.armed = True
        start = time.perf_counter()
        close = start + self.seconds

        def client(s: int) -> None:
            sess = sessions[s]
            while time.perf_counter() < close:
                n = len(steps[s])
                rec = drive.Record(Request(n, "step", None),
                                   submitted=time.perf_counter())
                try:
                    out = dec.step(sess, self.hidden(s, n))
                except Exception as e:       # a typed scheduler error
                    rec.seen = time.perf_counter()
                    rec.error = f"{type(e).__name__}: {e}"
                    steps[s].append((rec, None))
                    return
                rec.accepted = rec.seen = time.perf_counter()
                steps[s].append((rec, out))

        threads = [threading.Thread(target=client, args=(s,), daemon=True,
                                    name=f"bench-session-{s}")
                   for s in range(len(sessions))]
        with spans(drive.WINDOW):
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        end = time.perf_counter()
        counter.armed = False
        stats_after = drive.scheduler_stats(sched)
        self.served_steps = (sessions, steps)
        check = self.model_check(sessions, steps)
        print(f"chipbench: decode model check: {check['steps_checked']} "
              f"steps, largest relative error {check['relative_error']} "
              f"(limit {MODEL_LIMIT}), int8 step error "
              f"{check['quant_step_error']} (limit {QUANT_STEP_LIMIT}), "
              f"scale error {check['scale_error']} (limit {SCALE_LIMIT})",
              file=sys.stderr, flush=True)
        recs = [rec for per in steps for rec, _ in per]
        window = drive.Window(recs, start, end, end, stats_before,
                              stats_after, counter.count, sampler.items())
        window.decode = {"before": decode_before,
                         "after": dict(vars(dec.stats)),
                         "served_fused": served, **check}
        return window

    def checked_steps(self, steps: List[List]) -> Dict[int, List[int]]:
        """The seeded sample of answered steps per session."""
        k = int(self.traffic.params["check_steps_per_session"])
        out = {}
        for s, per in enumerate(steps):
            done = [i for i, (rec, o) in enumerate(per) if o is not None]
            rng = rng_for(self.traffic.seed, 8, s)
            out[s] = sorted(rng.choice(done, min(k, len(done)),
                                       replace=False).tolist())
        return out

    def reference(self, sess, out, s: int, i: int, forced=None,
                  **control) -> dict:
        """The benchmark's reference of step ``i`` of session ``s`` over
        the history the step read (on the host's CPU); ``control`` as in
        :data:`CONTROLS`."""
        import jax
        hist = [sess.history(li, out.pos) for li in range(self.layers)]
        try:
            host = jax.default_device(jax.devices("cpu")[0])
        except RuntimeError:                  # no CPU backend: the chip
            host = contextlib.nullcontext()
        with host:
            return ref.decode_step(self.params, self.hidden(s, i), out.pos,
                                   [h[0] for h in hist],
                                   [h[1] for h in hist], self.dims,
                                   forced=forced, **control)

    def model_check(self, sessions, steps,
                    control: Optional[str] = None) -> dict:
        """Compare the sampled steps with ``chipbench/qwen2_reference.py``
        forced with each step's int8 activations and scales; a step over a
        limit gets an error.  ``control`` (a key of :data:`CONTROLS`) puts
        that control in the program's place and records nothing.  Returns
        the steps compared and the largest reading of each limit."""
        reads = []
        for s, idx in self.checked_steps(steps).items():
            for i in idx:
                rec, out = steps[s][i]
                got = (self.reference(sessions[s], out, s, i,
                                      **CONTROLS[control])
                       if control else out.as_ref())
                want = self.reference(sessions[s], out, s, i,
                                      forced=got["quant"])
                read = {"relative_error": ref.compare(got, want),
                        "quant_step_error": want["quant_step_error"],
                        "scale_error": want["scale_error"]}
                reads.append(read)
                over = [f"{k} {read[k]} (limit {lim})" for k, lim in (
                    ("relative_error", MODEL_LIMIT),
                    ("quant_step_error", QUANT_STEP_LIMIT),
                    ("scale_error", SCALE_LIMIT)) if read[k] > lim]
                if over and not control:
                    rec.error = "model check: " + ", ".join(over)
        return {"steps_checked": len(reads),
                **{k: max((r[k] for r in reads), default=None)
                   for k in ("relative_error", "quant_step_error",
                             "scale_error")}}
