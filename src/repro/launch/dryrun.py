"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` pins this process to the CPU backend with 512 placeholder host
devices (the production meshes) before JAX initializes a backend; it never
touches an accelerator.  Importing the module changes nothing.

Per cell this produces, with zero array allocation:
  * ``compiled.memory_analysis()``  — proof the cell fits per-device HBM,
  * ``compiled.cost_analysis()``    — FLOPs / bytes for the roofline terms,
  * a collective-bytes breakdown parsed from the optimized SPMD HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand sizes; cost_analysis does not report these).

Artifacts are JSON files under ``experiments/dryrun/`` consumed by
``launch/roofline.py`` and the ``benchmarks`` tables (ROADMAP.md tracks
the open sweep items).  Already-complete cells are skipped (incremental
reruns).
"""

import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs import ARCHS, SHAPES, get, shapes_for
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.models.model_zoo import batch_axes, input_specs
from repro.sharding import rules_for_shape, shardings_for_tree
from repro.train import AdamWConfig, make_train_step
from repro.train.state import train_state_shardings


# --- cell construction ----------------------------------------------------------

# Remat-carry budget per device (HBM is 16G).  Larger budget => fewer
# microbatches => fewer per-microbatch FSDP gathers and grad reductions
# (measured on qwen train: n_micro 16 -> 8 halves the collective term);
# smaller budget => deeper models fit.  6 GiB balances the two for this
# matrix — the knob and its measured tradeoff are §Perf material.
CARRY_BUDGET_BYTES = 6 * 2 ** 30


def analytic_bytes_per_device(arch: str, shape_name: str, n_chips: int,
                              weight_bytes: int = 2,
                              model_shards: int = 16) -> float:
    """Closed-form HBM traffic per device for one decode step of this cell.

    Per device: its local weight shard (weights are TP-sharded over
    ``model`` and *replicated* over data under the decode rules, so local
    weights = total/model_shards, read once per token) + its slice of the
    KV/state cache (sharded over all chips) + O(B x D) activations.  This
    is the quantity TPU serving is sized by, and it sidesteps the CPU
    backend's bf16->f32 scatter legalization that inflates the HLO-derived
    byte count on decode cells (see the methodology note in
    ``launch/roofline.py``).  Train/prefill cells use the HLO-derived
    count instead (dots
    dominate and parse faithfully there).
    """
    cfg = get(arch)
    shape = SHAPES[shape_name]
    model = build(cfg)
    if shape.kind != "decode":
        return 0.0
    import math
    ring = shape_name.startswith("long") and cfg.window is not None
    c_abs, _ = model.cache_spec(shape.global_batch, shape.seq_len, ring=ring)
    cache_bytes = sum(
        jnp.dtype(l.dtype).itemsize * math.prod(l.shape)
        for l in jax.tree.leaves(c_abs)
    )
    param_bytes = model.param_count() * weight_bytes / model_shards
    act_bytes = 64 * shape.global_batch * cfg.d_model * 2 / n_chips
    return float(param_bytes + cache_bytes / n_chips + act_bytes)


def default_n_micro(cfg, shape, mesh) -> int:
    """Microbatch count so the per-device remat carry stack fits the budget.

    The dominant training residual is the per-layer input saved by the
    layer scan: layers x (B/dp) x S x D x 2 bytes.  Microbatching divides
    the live batch; the grad accumulator it adds is param-sized (already
    FSDP-sharded).
    """
    if shape.kind != "train":
        return 1
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    b_loc = max(shape.global_batch // dp, 1)
    layers = cfg.n_layers + cfg.encoder_layers
    carry = layers * b_loc * shape.seq_len * cfg.d_model * 2
    n = 1
    while carry / n > CARRY_BUDGET_BYTES and n < b_loc:
        n *= 2
    return n


def build_cell(arch: str, shape_name: str, mesh, *, n_micro: int = 1,
               ce_chunks: int = 8, weight_quant: str = ""):
    """Returns (fn, in_shardings, abstract_args) for one workload cell."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    model = build(cfg)
    rules = rules_for_shape(shape_name)

    if shape.kind == "train":
        abs_state, state_sh = train_state_shardings(model, mesh, rules)
        inputs = input_specs(cfg, shape)
        in_axes = batch_axes(cfg, "train")
        input_sh = shardings_for_tree(in_axes, inputs, mesh, rules)
        step = make_train_step(model, AdamWConfig(), n_micro=n_micro)
        return step, (state_sh, input_sh), (abs_state, inputs), rules

    # Inference weights are served in bf16 (the deployment dtype): half the
    # weight HBM traffic of the f32 training master copy.
    def _serving_params(abs_tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape,
                cfg.cdtype if jnp.issubdtype(s.dtype, jnp.floating)
                else s.dtype,
            ),
            abs_tree,
        )

    if shape.kind == "prefill":
        p_abs = _serving_params(model.abstract_params())
        p_sh = shardings_for_tree(model.param_axes(), p_abs, mesh, rules)
        inputs = input_specs(cfg, shape)
        in_axes = batch_axes(cfg, "prefill")
        input_sh = shardings_for_tree(in_axes, inputs, mesh, rules)
        c_abs, c_axes = model.cache_spec(shape.global_batch, shape.seq_len)
        c_sh = shardings_for_tree(c_axes, c_abs, mesh, rules)

        def prefill_fn(params, batch, cache):
            return model.prefill(params, batch, cache)

        return prefill_fn, (p_sh, input_sh, c_sh), (p_abs, inputs, c_abs), \
            rules

    # decode: one new token against a seq_len-deep cache
    ring = shape_name.startswith("long") and cfg.window is not None
    inputs = input_specs(cfg, shape)
    in_axes = batch_axes(cfg, "decode")
    input_sh = shardings_for_tree(in_axes, inputs, mesh, rules)
    c_abs, c_axes = model.cache_spec(shape.global_batch, shape.seq_len,
                                     ring=ring)
    c_sh = shardings_for_tree(c_axes, c_abs, mesh, rules)

    if weight_quant == "int8":
        # §Perf iteration 3: weight-only int8 serving (paper §4.4) — the
        # dequant (convert+scale) fuses into the consuming GEMMs, so the
        # weight HBM/collective traffic is the int8 payload.
        def q_abs(s):
            if jnp.issubdtype(s.dtype, jnp.floating):
                return jax.ShapeDtypeStruct(s.shape, jnp.int8)
            return s

        def s_abs(s):
            if jnp.issubdtype(s.dtype, jnp.floating):
                scale_shape = s.shape[-1:] if len(s.shape) > 1 else ()
                return jax.ShapeDtypeStruct(scale_shape, jnp.float32)
            return jax.ShapeDtypeStruct((), jnp.float32)

        raw_abs = model.abstract_params()
        p_abs = {"q": jax.tree.map(q_abs, raw_abs),
                 "s": jax.tree.map(s_abs, raw_abs)}
        axes = model.param_axes()
        scale_axes = jax.tree.map(
            lambda a: a[-1:] if len(a) > 1 else (),
            axes, is_leaf=lambda t: isinstance(t, tuple),
        )
        p_sh = {
            "q": shardings_for_tree(axes, p_abs["q"], mesh, rules),
            "s": shardings_for_tree(scale_axes, p_abs["s"], mesh, rules),
        }

        def decode_fn(pq, batch, cache):
            def deq(q, s):
                if jnp.issubdtype(q.dtype, jnp.signedinteger) and \
                        jnp.issubdtype(s.dtype, jnp.floating):
                    return (q.astype(jnp.float32) * s).astype(cfg.cdtype)
                return q
            params = jax.tree.map(deq, pq["q"], pq["s"])
            return model.decode_step(params, batch["token"], cache,
                                     batch["pos"], ring=ring)

        return decode_fn, (p_sh, input_sh, c_sh), (p_abs, inputs, c_abs), \
            rules

    p_abs = _serving_params(model.abstract_params())
    p_sh = shardings_for_tree(model.param_axes(), p_abs, mesh, rules)

    def decode_fn(params, batch, cache):
        return model.decode_step(params, batch["token"], cache,
                                 batch["pos"], ring=ring)

    return decode_fn, (p_sh, input_sh, c_sh), (p_abs, inputs, c_abs), rules


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = "experiments/dryrun",
             n_micro: Optional[int] = None, verbose: bool = True,
             variant: str = "", **build_kw) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (
        f"__{variant}" if variant else ""
    )
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, cell_id + ".json")

    mesh = make_production_mesh(multi_pod=multi_pod)
    if n_micro is None:
        n_micro = default_n_micro(get(arch), SHAPES[shape_name], mesh)
    t0 = time.time()
    fn, in_sh, abs_args, rules = build_cell(
        arch, shape_name, mesh, n_micro=n_micro, **build_kw
    )
    with sharding.activate(mesh, rules):
        jitted = jax.jit(fn, in_shardings=in_sh)
        lowered = jitted.lower(*abs_args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware static analysis (cost_analysis counts loop bodies
    # once; see launch/hlo_cost.py)
    static = hlo_cost.analyze(hlo)

    n_chips = mesh.devices.size
    analytic = analytic_bytes_per_device(
        arch, shape_name, int(n_chips),
        weight_bytes=1 if "int8" in variant else 2,
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "n_chips": int(n_chips),
        "n_micro": int(n_micro),
        "flops_per_device": float(static.dot_flops),
        "bytes_per_device": float(static.bytes),
        "bytes_analytic_per_device": analytic,
        "collectives": {
            **static.collectives, "total_bytes": float(
                static.collective_bytes),
        },
        "xla_cost_analysis": {   # loop bodies counted once — cross-check only
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    if verbose:
        mb = record["memory"]
        print(
            f"[ok] {cell_id}: flops/dev={record['flops_per_device']:.3e} "
            f"bytes/dev={record['bytes_per_device']:.3e} "
            f"coll/dev={record['collectives']['total_bytes']:.3e}B "
            f"args={mb['argument_bytes']/2**30:.2f}GiB "
            f"temp={mb['temp_bytes']/2**30:.2f}GiB n_micro={n_micro} "
            f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)",
            flush=True,
        )
    return record


def cells(archs=None, shapes=None, meshes=("pod16x16", "pod2x16x16")):
    for arch in (archs or ARCHS):
        cfg = get(arch)
        for shape_name in (shapes or shapes_for(cfg)):
            if shapes is None and shape_name not in shapes_for(cfg):
                continue
            for mesh_name in meshes:
                yield arch, shape_name, mesh_name == "pod2x16x16"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="single arch id (default all)")
    ap.add_argument("--shape", default=None, help="single shape (default all)")
    ap.add_argument("--mesh", default=None,
                    choices=[None, "pod16x16", "pod2x16x16"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have artifacts")
    args = ap.parse_args(argv)
    flags = "--xla_force_host_platform_device_count=512"
    if os.environ.get("REPRO_EXTRA_XLA_FLAGS"):
        flags += " " + os.environ["REPRO_EXTRA_XLA_FLAGS"]
    os.environ["XLA_FLAGS"] = flags   # read when the backend initializes
    jax.config.update("jax_platforms", "cpu")

    archs = [args.arch] if args.arch else None
    shapes = [args.shape] if args.shape else None
    meshes = (args.mesh,) if args.mesh else ("pod16x16", "pod2x16x16")

    failures = []
    for arch, shape_name, multi_pod in cells(archs, shapes, meshes):
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        cell_id = f"{arch}__{shape_name}__{mesh_name}"
        out_path = os.path.join(args.out, cell_id + ".json")
        if os.path.exists(out_path) and not args.force:
            print(f"[skip] {cell_id} (artifact exists)", flush=True)
            continue
        try:
            run_cell(arch, shape_name, multi_pod=multi_pod, out_dir=args.out)
        except Exception:
            traceback.print_exc()
            failures.append(cell_id)
    if failures:
        print(f"FAILED cells ({len(failures)}): {failures}", flush=True)
        sys.exit(1)
    print("dry-run complete", flush=True)


if __name__ == "__main__":
    main()
