"""Distributed behaviour on an 8-device host mesh (subprocess isolation).

Device count is locked at first jax init, so every multi-device scenario
runs in its own python subprocess with XLA_FLAGS set.  Each scenario script
asserts internally and exits nonzero on failure.
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # multi-device subprocesses, minutes each

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import get_smoke, ShapeSpec
        from repro.models import build
        from repro.models.model_zoo import materialize_inputs, batch_axes, input_specs
        from repro.sharding import DEFAULT_RULES, shardings_for_tree
        from repro.train import AdamWConfig, make_train_step
        from repro.train.state import init_train_state, train_state_shardings
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("yi-9b")
        m = build(cfg)
        rng = jax.random.PRNGKey(0)
        params = m.init(rng)
        batch = materialize_inputs(rng, cfg, ShapeSpec("t", 16, 8, "train"))
        opt = AdamWConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=10)

        # single-device reference
        s_ref, met_ref = jax.jit(make_train_step(m, opt))(init_train_state(params), batch)

        mesh = make_host_mesh()   # (4, 2) or (2, 4) over 8 devices
        abs_state, st_sh = train_state_shardings(m, mesh)
        in_axes = batch_axes(cfg, "train")
        b_sh = shardings_for_tree(in_axes, input_specs(cfg, ShapeSpec("t", 16, 8, "train")), mesh)
        with sharding.activate(mesh, DEFAULT_RULES):
            step = jax.jit(make_train_step(m, opt), in_shardings=(st_sh, b_sh))
            state0 = jax.device_put(init_train_state(params), st_sh)
            batch_d = jax.device_put(batch, b_sh)
            s_sh, met_sh = step(state0, batch_d)
        np.testing.assert_allclose(float(met_ref["loss"]), float(met_sh["loss"]), rtol=1e-4)
        a = np.asarray(jax.device_get(s_sh.params["final_norm"]["w"]))
        b = np.asarray(s_ref.params["final_norm"]["w"])
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
        print("sharded == single-device OK")
    """)


def test_elastic_checkpoint_resharding():
    """Save on a (4,2) mesh, restore onto (2,2) subset — mesh-agnostic files."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint import save, restore
        from repro.sharding import DEFAULT_RULES, shardings_for_tree
        from repro.configs import get_smoke
        from repro.models import build

        cfg = get_smoke("yi-9b")
        m = build(cfg)
        params = m.init(jax.random.PRNGKey(0))

        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sh_a = shardings_for_tree(m.param_axes(), m.abstract_params(), mesh_a)
        p_a = jax.device_put(params, sh_a)

        d = tempfile.mkdtemp()
        save(p_a, d, 1)

        # "elastic downsize": rebuild over 4 devices only
        import numpy as _np
        devs = _np.asarray(jax.devices()[:4]).reshape(2, 2)
        from jax.sharding import Mesh
        mesh_b = Mesh(devs, ("data", "model"))
        sh_b = shardings_for_tree(m.param_axes(), m.abstract_params(), mesh_b)
        p_b = restore(d, m.abstract_params(), shardings=sh_b)
        for x, y in zip(jax.tree.leaves(p_b), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("elastic restore OK")
    """)


def test_pod_compressed_train_step():
    """int8 pod-compressed step runs on a (2,2,2) mesh and tracks the
    uncompressed step closely (error feedback)."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import get_smoke, ShapeSpec
        from repro.models import build
        from repro.models.model_zoo import materialize_inputs
        from repro.train import AdamWConfig, make_train_step
        from repro.train.trainer import make_train_step_pod_compressed
        from repro.train.state import init_train_state

        cfg = get_smoke("yi-9b")
        m = build(cfg)
        rng = jax.random.PRNGKey(0)
        params = m.init(rng)
        batch = materialize_inputs(rng, cfg, ShapeSpec("t", 16, 8, "train"))
        opt = AdamWConfig(peak_lr=1e-3, warmup_steps=0, decay_steps=100)

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        with sharding.activate(mesh):
            comp = jax.jit(make_train_step_pod_compressed(m, opt, mesh))
            ref = jax.jit(make_train_step(m, opt))
            s_c = init_train_state(params, compression=True)
            s_r = init_train_state(params)
            for i in range(3):
                s_c, met_c = comp(s_c, batch)
                s_r, met_r = ref(s_r, batch)
        # same data => compressed trajectory tracks exact one
        np.testing.assert_allclose(float(met_c["loss"]), float(met_r["loss"]), rtol=2e-2)
        a = np.asarray(jax.device_get(s_c.params["final_norm"]["w"]))
        b = np.asarray(s_r.params["final_norm"]["w"])
        np.testing.assert_allclose(a, b, rtol=5e-2, atol=1e-4)
        print("pod-compressed OK; loss", float(met_c["loss"]), float(met_r["loss"]))
    """)


def test_compressed_allreduce_exactness():
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.sharding import shard_map
        from repro.train.compression import compressed_allreduce

        mesh = jax.make_mesh((8,), ("pod",))
        x = jnp.arange(8 * 32, dtype=jnp.float32).reshape(8, 32) / 17.0
        err = jnp.zeros_like(x)

        def f(x, e):
            return compressed_allreduce(x[0], e[0], "pod")

        mean, new_err = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P(), P("pod")), check_vma=False,
        ))(x, err)
        want = np.asarray(x).mean(0)
        got = np.asarray(mean)
        tol = np.abs(np.asarray(x)).max() / 127.0
        assert np.abs(got - want).max() <= tol, (got, want)
        print("compressed allreduce OK")
    """)


def test_moe_ep_matches_reference():
    """Fully-manual 2D EP == single-device sort dispatch (ample capacity)."""
    run_script("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import get_smoke, ShapeSpec
        from repro.models import build
        from repro.models.model_zoo import materialize_inputs
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("moonshot-v1-16b-a3b")
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        m = build(cfg)
        rng = jax.random.PRNGKey(0)
        params = m.init(rng)
        batch = materialize_inputs(rng, cfg, ShapeSpec("t", 16, 8, "train"))
        from repro.models import transformer
        ref, _ = transformer.forward(params, batch, cfg, moe_strategy="sort")

        mesh = make_host_mesh()   # (4, 2) data x model; experts 8 % 2 == 0
        with sharding.activate(mesh):
            got, _ = jax.jit(lambda p, b: transformer.forward(
                p, b, cfg, moe_strategy="ep"))(params, batch)
        # MoE routing is discontinuous: bf16 noise can flip a borderline
        # token's expert between paths, so compare in bulk (99th pct) plus
        # a loose max bound, not elementwise-tight.
        diff = np.abs(np.asarray(ref) - np.asarray(got))
        assert np.quantile(diff, 0.99) < 3e-2, np.quantile(diff, 0.99)
        assert diff.mean() < 5e-3, diff.mean()
        assert diff.max() < 1.0, diff.max()
        print("moe ep parity OK")
    """)


def test_sharded_decode_step():
    """Decode with cache sharded over a host mesh == single device decode."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding
        from repro.configs import get_smoke
        from repro.models import build
        from repro.sharding import DECODE_RULES, shardings_for_tree
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("granite-34b")   # MQA kv=1: cache seq-sharding path
        m = build(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, L = 4, 32
        cache = m.init_cache(B, L)
        tok = jnp.asarray([1, 2, 3, 4], jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        ref_logits, _ = m.decode_step(params, tok, cache, pos)

        mesh = make_host_mesh()
        c_abs, c_axes = m.cache_spec(B, L)
        c_sh = shardings_for_tree(c_axes, c_abs, mesh, DECODE_RULES)
        with sharding.activate(mesh, DECODE_RULES):
            cache_d = jax.device_put(m.init_cache(B, L), c_sh)
            logits, _ = jax.jit(m.decode_step)(params, tok, cache_d, pos)
        np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits), rtol=2e-2, atol=2e-2)
        print("sharded decode OK")
    """)
