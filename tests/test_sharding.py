"""Sharding rules + multi-device behavior (subprocess with host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.parallel.axes import DEFAULT_RULES, spec_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_spec_for_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    # divisible -> sharded
    s = spec_for(("embed", "heads"), (8192, 8192), mesh, DEFAULT_RULES)
    assert tuple(s) == ("data", "model")
    # a projection dim not divisible by 16 -> replicated
    s = spec_for(("embed", "heads"), (896, 14 * 9), mesh, DEFAULT_RULES)
    assert tuple(s) == ("data", None)
    # each mesh axis used at most once
    s = spec_for(("act_heads", "seq"), (64, 4096), mesh, DEFAULT_RULES)
    assert tuple(s) == ("model", None)
    # fallback cascade: heads fail -> seq takes model
    s = spec_for(("act_heads", "seq"), (56, 4096), mesh, DEFAULT_RULES)
    assert tuple(s) == (None, "model")


def test_spec_for_pod_axis_dropped_on_single_pod():
    mesh = FakeMesh({"data": 16, "model": 16})
    s = spec_for(("batch", None), (256, 128), mesh, DEFAULT_RULES)
    assert tuple(s) == (("data",), None) or tuple(s) == ("data", None)
    mesh2 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    s2 = spec_for(("batch", None), (256, 128), mesh2, DEFAULT_RULES)
    assert tuple(s2)[0] == ("pod", "data")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_data_parallel_loss_matches_single_device():
    """The sharded train step computes the same loss as 1 device."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import jitted_cell
        from repro.models import LM
        from repro.optim import adamw_init
        from repro.parallel.axes import sharding_context

        cfg = get_config("qwen2-0.5b", reduced=True)
        cell = ShapeCell("t", 32, 8, "train")
        model = LM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 1, 500),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 1, 500),
            "loss_mask": jnp.ones((8, 32), jnp.float32),
            "positions": jnp.tile(jnp.arange(32), (8, 1)),
            "segment_ids": jnp.ones((8, 32), jnp.int32),
        }
        losses = []
        for shape in ({"data": 1, "model": 1}, {"data": 4, "model": 2}):
            mesh = make_mesh(shape)
            with sharding_context(mesh) as ctx:
                step, _ = jitted_cell(cfg, cell, ctx)
                p, o, m = step(jax.tree.map(jnp.copy, params),
                               adamw_init(params), dict(batch))
                losses.append(float(m["loss"]))
        print("LOSSES", losses[0], losses[1])
        assert abs(losses[0] - losses[1]) < 2e-2, losses
    """)
    assert "LOSSES" in out


@pytest.mark.slow
def test_compressed_allreduce_subprocess():
    """int8 all-gather mean over 4 devices: small error vs exact."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.parallel.compression import compressed_allreduce_mean
        mesh = make_mesh({"data": 4})
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 64)).astype(np.float32))
        f = jax.shard_map(
            lambda v: compressed_allreduce_mean(v[0], "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
        got = f(x)
        want = x.mean(axis=0)
        err = float(jnp.abs(got - want).max())
        amax = float(jnp.abs(x).max())
        print("ERR", err, "BOUND", amax / 127 * 2)
        assert err <= amax / 127.0 * 2 + 1e-6
    """, devices=4)
    assert "ERR" in out


@pytest.mark.slow
def test_dryrun_entrypoint_small():
    """The dry-run module itself runs end-to-end (tiny mesh via env)."""
    out = _run_subprocess("""
        import os, dataclasses, jax
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import jitted_cell
        from repro.launch import hlo_analysis
        from repro.parallel.axes import sharding_context

        cfg = get_config("qwen2-0.5b", reduced=True)
        cell = ShapeCell("t", 64, 8, "train")
        mesh = make_mesh({"data": 2, "model": 4})
        with sharding_context(mesh) as ctx:
            step, args = jitted_cell(cfg, cell, ctx)
            compiled = step.lower(*args).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            coll = hlo_analysis.collective_bytes(compiled.as_text())
            assert cost.get("flops", 0) > 0
            assert coll["total"] > 0, coll
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes > 0
        print("DRYRUN_OK", coll["total"])
    """)
    assert "DRYRUN_OK" in out


def test_collective_bytes_parser():
    from repro.launch.hlo_analysis import collective_bytes
    hlo = """
      %all-reduce.1 = f32[1024,8]{1,0} all-reduce(f32[1024,8]{1,0} %x)
      %ag = bf16[16,256]{1,0} all-gather(bf16[2,256]{1,0} %y)
      %t = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b)
      %rs = f32[32]{0} reduce-scatter(f32[256]{0} %z)
      %fusion.2 = f32[9]{0} fusion(%w), calls=%all_reduce_like
      %cp = u8[100]{0} collective-permute(u8[100]{0} %q)
    """
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 1024 * 8 * 4 + 128 * 4 + 64 * 4
    assert got["all-gather"] == 16 * 256 * 2
    assert got["reduce-scatter"] == 32 * 4
    assert got["collective-permute"] == 100
    assert got["total"] == sum(got[k] for k in
                               ("all-reduce", "all-gather",
                                "reduce-scatter", "all-to-all",
                                "collective-permute"))


from _hypothesis_compat import given, settings, st


@settings(max_examples=30, deadline=None)
@given(st.lists(st.sampled_from(["embed", "heads", "mlp", "vocab",
                                 "batch", "seq", None]),
                min_size=1, max_size=4),
       st.lists(st.sampled_from([1, 8, 14, 16, 56, 256, 4096]),
                min_size=1, max_size=4))
def test_spec_for_never_reuses_axes_and_always_divides(names, sizes):
    """Property: any logical spec resolves to a valid PartitionSpec —
    every mesh axis used at most once, every sharded dim divisible."""
    n = min(len(names), len(sizes))
    names, sizes = names[:n], sizes[:n]
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = spec_for(tuple(names), tuple(sizes), mesh, DEFAULT_RULES)
    used = []
    for part, size in zip(tuple(spec), sizes):
        if part is None:
            continue
        axes = (part,) if isinstance(part, str) else tuple(part)
        extent = 1
        for a in axes:
            assert a not in used, (spec, names, sizes)
            used.append(a)
            extent *= mesh.shape[a]
        assert size % extent == 0, (spec, names, sizes)


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    """4-stage GPipe over 4 host devices: forward AND grads match the
    sequential composition; bubble math sane."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import bubble_fraction, pipeline_apply

        S, M, MB, D = 4, 8, 2, 16
        mesh = make_mesh({"stage": S})
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, D, D)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

        def stage_fn(ws, h):
            return jnp.tanh(h @ ws["w"])

        apply = pipeline_apply(stage_fn, mesh, S)

        def pp_loss(params, xs):
            y = apply(params, xs)
            return jnp.mean(y ** 2), y

        (pl, py), pg = jax.value_and_grad(pp_loss, has_aux=True)(
            {"w": w}, x)

        def seq_loss(params, xs):
            h = xs.reshape(M * MB, D)
            for s in range(S):
                h = jnp.tanh(h @ params["w"][s])
            return jnp.mean(h ** 2), h.reshape(M, MB, D)

        (sl, sy), sg = jax.value_and_grad(seq_loss, has_aux=True)(
            {"w": w}, x)

        np.testing.assert_allclose(np.asarray(py), np.asarray(sy),
                                   rtol=1e-5, atol=1e-5)
        assert abs(float(pl) - float(sl)) < 1e-6
        np.testing.assert_allclose(np.asarray(pg["w"]),
                                   np.asarray(sg["w"]),
                                   rtol=1e-4, atol=1e-5)
        assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
        print("PIPELINE_OK", float(pl))
    """, devices=4)
    assert "PIPELINE_OK" in out
