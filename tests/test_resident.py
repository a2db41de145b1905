"""Device images on the serve path: a request whose memory image is a
``jax.Array`` is stacked, run and answered on the device
(docs/SERVING.md "Device images").

Each path of the scheduler (the fused tier alone and batched, the VM
tier before promotion, a retry and a bisected batch after a failed
dispatch, the oracle rung of the degradation ladder, the fault injector
and the auditor) serves the same requests twice, once as
numpy images and once as device images, and the answers must agree bit
for bit: memory image, every register with its dtype, and the Tag.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.isa import DType
from repro.core.machine import MVEConfig
from repro.core.tracing import counting
from repro.frontend import SEQ, KernelBuilder
from repro.resilience import FaultInjector, FaultPlan, FaultSpec
from repro.resilience.breaker import CircuitBreaker
from repro.runtime.scheduler import MVEScheduler, SchedulerStats

CFG = MVEConfig()
N = 64


def _kernel():
    """``x > y`` into the Tag, a predicated sum (lanes where the Tag is
    clear keep zero) and an int32 product, so the answer has float and
    integer registers and a mixed Tag."""
    b = KernelBuilder("resident_probe")
    x = b.input("x", (N,))
    y = b.input("y", (N,))
    z = b.output("z", (N,))
    b.width(32)
    b.dims(N)
    xv, yv = x.load(SEQ), y.load(SEQ)
    xv.gt(yv)
    s = b.add(xv, yv, predicated=True)
    xv.astype(DType.DW) * 3
    z.store(s * 2.0 + xv, SEQ)
    return b.build()


@pytest.fixture(scope="module")
def kernel():
    return _kernel()


def _images(kernel, n, seed=0):
    rng = np.random.default_rng(seed)
    return [np.asarray(kernel.pack({"x": rng.standard_normal(N),
                                    "y": rng.standard_normal(N)}),
                       np.float32) for _ in range(n)]


def _serve(kernel, images, device, plan=None, **kw):
    """Submit every image (as device arrays when ``device``), serve them
    in one drain; the results and the scheduler's counters."""
    inj = FaultInjector(plan) if plan is not None else None
    with MVEScheduler(CFG, injector=inj, **kw) as s:
        s._sleep = lambda _: None
        tickets = [s.submit(kernel.program,
                            jnp.asarray(img) if device else img)
                   for img in images]
        s.drain()
        return [t.result(timeout=60) for t in tickets], dict(vars(s.stats))


def _sticky(tier):
    return FaultSpec(site="dispatch", kind="error", rid=0, tier=tier,
                     times=-1)


def _flip():
    return FaultSpec(site="finalize", kind="bitflip", rid=1, word=3, bit=30)


def _fused_dispatch_error():
    return FaultPlan([FaultSpec(site="dispatch", kind="error", tier="fused")])


#: path -> (requests, scheduler settings, fault plan, answers that stay
#: on the device)
PATHS = {
    "fused_single": (1, dict(promote_after=1), None, 1),
    "fused_batch3": (3, dict(promote_after=1), None, 3),
    "fused_batch16": (16, dict(promote_after=1), None, 16),
    "vm_single": (1, dict(promote_after=None), None, 0),
    "vm_batch3": (3, dict(promote_after=None), None, 0),
    "oracle": (1, dict(promote_after=1, breaker=CircuitBreaker(
        threshold=1, cooldown_s=60.0)),
        FaultPlan([_sticky("fused"), _sticky("vm")]), 1),
    "audited": (3, dict(promote_after=1, audit_rate=1.0), None, 3),
    "bitflip": (3, dict(promote_after=1), FaultPlan([_flip()]), 0),
    "bitflip_audited": (3, dict(promote_after=1, audit_rate=1.0),
                        FaultPlan([_flip()]), 0),
    "retried": (1, dict(promote_after=1), _fused_dispatch_error(), 1),
    "bisected": (3, dict(promote_after=1), _fused_dispatch_error(), 3),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_device_images_answer_as_host_images(kernel, path):
    n, kw, plan, stayed = PATHS[path]
    images = _images(kernel, n, seed=len(path))
    host, hstats = _serve(kernel, images, False, plan, **kw)
    dev, dstats = _serve(kernel, images, True, plan, **kw)
    for h, d in zip(host, dev):
        assert (d.tier, d.batch_size) == (h.tier, h.batch_size)
        assert isinstance(d.memory, jax.Array)
        assert isinstance(d.tag, jax.Array)
        assert all(isinstance(v, jax.Array) for v in d.regs.values())
        np.testing.assert_array_equal(np.asarray(d.memory), h.memory)
        np.testing.assert_array_equal(np.asarray(d.tag),
                                      np.asarray(h.tag))
        assert sorted(d.regs) == sorted(h.regs)
        for r, v in h.regs.items():
            assert np.asarray(d.regs[r]).dtype == np.asarray(v).dtype
            np.testing.assert_array_equal(np.asarray(d.regs[r]),
                                          np.asarray(v))
    assert dstats["resident_requests"] == stayed
    assert hstats["resident_requests"] == 0
    if path.startswith("fused"):
        # nothing crosses: no image, register or Tag bytes either way
        assert dstats["h2d_bytes"] == dstats["d2h_bytes"] == 0
        assert hstats["h2d_bytes"] > 0 and hstats["d2h_bytes"] > 0


def test_paths_are_what_they_say(kernel):
    """The cases above reach the tier, batch and recovery they name."""
    img = _images(kernel, 16)
    res, st = _serve(kernel, img[:3], True, **PATHS["fused_batch3"][1])
    assert {r.tier for r in res} == {"fused"} and st["fused_batches"] == 1
    assert res[0].batch_size == 3
    res, st = _serve(kernel, img, True, **PATHS["fused_batch16"][1])
    assert st["fused_batches"] == 1 and st["max_batch_seen"] == 16
    res, st = _serve(kernel, img[:3], True, **PATHS["vm_batch3"][1])
    assert {r.tier for r in res} == {"vm"} and st["vm_batches"] == 1
    _, kw, plan, _ = PATHS["oracle"]
    res, st = _serve(kernel, img[:1], True, plan, **kw)
    assert res[0].tier == "oracle" and st["oracle_serves"] == 1
    _, kw, plan, _ = PATHS["bitflip_audited"]
    res, st = _serve(kernel, img[:3], True, plan, **kw)
    assert st["audit_checked"] == 3 and st["audit_corrected"] == 1
    _, kw, plan, _ = PATHS["retried"]
    res, st = _serve(kernel, img[:1], True, plan, **kw)
    assert res[0].tier == "fused"
    assert st["retries"] == 1 and st["recovered"] == 1
    _, kw, plan, _ = PATHS["bisected"]
    res, st = _serve(kernel, img[:3], True, plan, **kw)
    assert {r.tier for r in res} == {"fused"}
    assert st["bisections"] == 1 and st["recovered"] == 3


def test_a_batch_never_mixes_host_and_device_images(kernel):
    images = _images(kernel, 6)
    with MVEScheduler(CFG, promote_after=1) as s:
        tickets = [s.submit(kernel.program,
                            jnp.asarray(img) if i % 2 else img)
                   for i, img in enumerate(images)]
        s.drain()
        assert s.stats.fused_batches == 2
        assert s.stats.resident_requests == 3
    for i, t in enumerate(tickets):
        r = t.result()
        assert r.batch_size == 3
        assert isinstance(r.memory, jax.Array) == bool(i % 2)
    cp = engine.compile_program(kernel.program, CFG, mode="fused")
    assert cp.batch_group_key(images[0])[-1] is False
    assert cp.batch_group_key(jnp.asarray(images[0]))[-1] is True


def test_vm_fetch_of_a_device_image_is_counted(kernel):
    """The VM tier pads a device image on the host: that fetch counts in
    ``d2h_bytes``, and nothing else differs from a host image's copies
    back."""
    img = _images(kernel, 1)[0]
    cp = engine.compile_program(kernel.program, CFG, mode="vm")
    assert cp.mode == "vm"
    host, dev = SchedulerStats(), SchedulerStats()
    with counting(host):
        cp.run(img)
    with counting(dev):
        cp.run(jnp.asarray(img))
    assert dev.d2h_bytes - host.d2h_bytes == img.nbytes
    _, hst = _serve(kernel, [img], False, promote_after=None)
    _, dst = _serve(kernel, [img], True, promote_after=None)
    assert dst["d2h_bytes"] - hst["d2h_bytes"] == img.nbytes


def test_warm_resident_compiles_the_device_batch_path(kernel):
    """After ``warm_resident`` a device batch at any bucket compiles
    nothing."""
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw)

    jax.monitoring.register_event_duration_secs_listener(listen)
    images = _images(kernel, 8, seed=3)
    with MVEScheduler(CFG, promote_after=1, max_batch=8) as s:
        s.warm_resident(kernel.program, images[0].size)
        fused = engine.compile_program(kernel.program, CFG, mode="fused")
        fused.run(images[0])
        for b in (2, 4, 8):
            fused.run_batch(np.stack(images[:b]))
        compiles.clear()
        for n in (1, 2, 3, 8):
            tickets = [s.submit(kernel.program, jnp.asarray(img))
                       for img in images[:n]]
            s.drain()
            for t in tickets:
                r = t.result()
                assert isinstance(r.memory, jax.Array)
                assert isinstance(r.tag, jax.Array) and r.regs
    assert compiles == []
