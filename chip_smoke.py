#!/usr/bin/env python
"""Chip smoke run: the lane-detection service end to end on a TPU.

    python chip_smoke.py              # one chip: the served path, checked
    python chip_smoke.py --chips 4    # four chips: the replica router only

One process drives the service through the entry points a user calls
(``DetectionService.submit`` -> ``run`` -> a terminal answer), with the
Pallas kernels compiled for the chip, at the service's widest buckets
(480x640 and 240x320):

  * kernels: each Pallas kernel against its jnp reference on the host;
  * warm-up: every plan binding compiles (``DetectionService.warm_up``)
    and a few frames per bucket run, so the phases below compile nothing;
  * phase one: 64 seeded sessionless requests over every scenario family
    at both buckets, each with a deadline;
  * phase two: one seeded 48-frame drive-cycle session at 480x640, long
    enough that the tracker's gated and fused plans dispatch warm, under
    ``jax.transfer_guard("disallow")``.

Every answer is checked against the same service run with the jnp
reference kernels (``impl="xla"``) on the host CPU in this process:
identical valid (rho, theta) peaks per request, or peaks within one rho
and one theta bin where a vote landed one rho bin over (the vote's f32 rho
sum rounds differently on the two backends; the counts are printed), and
per-family F1 against the planted truth no lower than the reference's
(and at or above the family's ``f1_floor`` wherever the reference is).
The served programs must contain the chip kernels (``tpu_custom_call``),
and no compile may happen after warm-up.

``--chips 4`` runs only the router path: ``ShardedDetectionService`` with
four replicas on the four chips of a 2x2 host, the same requests through
one replica as the comparison, every replica on its own chip with at
least one batch dispatched, and agreement between the two.

Everything worth reading goes to stdout first; the last line is one JSON
object ``{"ok": true, "device": {...}}``.  Any failed check exits non-zero
and prints no such line, as does a host with no TPU.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DEADLINE_S = 30.0          # generous: every request must be served in full
SHAPES = ((480, 640), (240, 320))   # the service's widest buckets
N_PHASE_ONE = 64
N_CYCLE = 48


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def setup():
    """Import the repo, turn the compile cache on, and demand a TPU."""
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro next to {Path(__file__).name}: run it from a "
             f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    forced = os.environ.get("REPRO_KERNEL_IMPL")
    if forced and forced != "pallas":
        fail(f"REPRO_KERNEL_IMPL={forced!r}: the chip run takes the Pallas "
             f"kernels only")
    import jax

    # The host reference runs on the CPU backend of this same process.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"JAX found no TPU (default backend {backend!r})")
    return jax, cache_dir


def count_compiles(jax):
    """A live count of executables built in this process (persistent-cache
    hits included): JAX records one backend-compile event per build."""
    from jax._src import dispatch

    counter = collections.Counter()

    def listener(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            counter[kw.get("fun_name", "?")] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return counter


def service_config(impl=None):
    from repro.core import CannyConfig, HoughConfig, PipelineConfig

    return PipelineConfig(
        canny=CannyConfig(impl=impl),
        hough=HoughConfig(compact=True, max_edges="auto", impl=impl),
    )


def service_kwargs():
    from repro.core import ControlConfig
    from repro.serve.detection import DEFAULT_BUCKETS

    return dict(buckets=DEFAULT_BUCKETS, batch_size=4, gate_band=40,
                fused_corridors=4, steering=ControlConfig())


def phase_one_frames(seed: int):
    """(uid, family, frame, truth) for 64 sessionless requests: every
    family at both buckets, seeded."""
    from repro.data import make_scenario, scenario_names

    fams = scenario_names()
    out = []
    for i in range(N_PHASE_ONE):
        fam = fams[(i // 2) % len(fams)]
        h, w = SHAPES[i % len(SHAPES)]
        scene = make_scenario(fam, h, w, seed=seed * 1000 + i)
        out.append((i, fam, scene.image, scene.lines_rho_theta))
    return out


def run_sessionless(svc, frames, *, deadline_s):
    from repro.serve.detection import DetectionRequest

    reqs = [DetectionRequest(uid=uid, frame=img, deadline_s=deadline_s)
            for uid, _, img, _ in frames]
    for r in reqs:
        svc.submit(r)
    svc.run()
    return reqs


def run_session(svc, cycle, session_id, *, deadline_s, uid0):
    from repro.serve.detection import DetectionRequest

    reqs = []
    for fr in cycle.frames:
        req = DetectionRequest(uid=uid0 + fr.t, frame=fr.scene.image,
                               session_id=session_id, deadline_s=deadline_s)
        svc.submit(req)
        svc.run()
        reqs.append(req)
    return reqs


def require_served(reqs, what):
    from repro.serve.detection import RequestStatus

    counts = collections.Counter(r.status.value for r in reqs)
    print(f"  {what}: {len(reqs)} requests, statuses {dict(counts)}")
    if not all(r.is_terminal for r in reqs):
        fail(f"{what}: a request never reached a terminal status")
    if counts[RequestStatus.DONE.value] != len(reqs):
        fail(f"{what}: not every request was served in full: "
             f"{dict(counts)}")


def valid_peaks(req):
    import numpy as np

    peaks = np.asarray(req.result.peaks, np.float64)
    return peaks[np.asarray(req.result.valid, bool)]


def compare(got, want, *, rho_bin, theta_bin):
    """(n_identical, n_within_one_bin, n_requests) over paired requests."""
    import numpy as np

    same = near = 0
    for g, w in zip(got, want):
        a, b = valid_peaks(g), valid_peaks(w)
        if a.shape == b.shape and np.array_equal(a, b):
            same += 1
            continue
        if a.shape == b.shape:
            used = set()
            for p in a:
                hit = next((j for j, q in enumerate(b) if j not in used
                            and abs(p[0] - q[0]) <= rho_bin
                            and abs(p[1] - q[1]) <= theta_bin), None)
                if hit is None:
                    break
                used.add(hit)
            else:
                near += 1
    return same, near, len(got)


def family_f1(reqs, families, truths):
    from repro.core.metrics import aggregate_scores, score_frame

    by_fam = collections.defaultdict(list)
    for r, fam, truth in zip(reqs, families, truths):
        by_fam[fam].append(score_frame(r.result.peaks, r.result.valid,
                                       truth))
    return {f: aggregate_scores(s)["f1"] for f, s in by_fam.items()}


def kernel_checks(jax, seed: int) -> list[str]:
    """Each chip kernel against its jnp reference on the host CPU, on one
    batch of seeded frames at the smaller bucket; returns the kernels that
    fail.  The convs and kernel A are exact in f32 on integer-valued
    frames, so they must be equal.  The vote's rho is an f32 sum that the
    CPU's dot fuses into a multiply-add and the chip does not, so a vote
    whose rho sits within an ulp of a bin edge may land one rho bin over:
    the vote check counts those and fails on anything else."""
    import numpy as np
    from repro.core import CannyConfig
    from repro.core.canny import gradient_masks
    from repro.core.hough import HoughConfig, hough_trig, rho_bins
    from repro.data import make_scenario
    from repro.kernels import ops

    h, w = SHAPES[-1]
    scenes = [make_scenario(f, h, w, seed=seed * 1000 + 900 + i)
              for i, f in enumerate(("straight", "night", "curved",
                                     "multilane"))]
    frames = np.stack([sc.image for sc in scenes]).astype(np.float32)
    rows = [[math.cos(t), math.sin(t), r - 12.0, r + 12.0]
            for sc in scenes[:1] for r, t in sc.lines_rho_theta]
    corridors = np.asarray((rows * 4)[:4], np.float32)
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    cfg, hcfg = CannyConfig(), HoughConfig()
    gauss, sobel = gradient_masks(cfg)
    trig = hough_trig(h, w, hcfg)

    def both(fn):
        chip = fn(jax.device_put(frames, tpu), "pallas")
        ref = fn(jax.device_put(frames, cpu), "xla")
        return np.asarray(chip), np.asarray(ref)

    def conv(x, impl):
        s = ops.conv2d_gemm(x, gauss, impl=impl)[:, 0]
        return ops.conv2d_gemm(s, sobel, impl=impl)

    def weights(cor):
        return lambda x, impl: ops.fused_weights(
            x, cor, cfg=cfg, edge_threshold=hcfg.edge_threshold, impl=impl)

    def vote(x, impl):
        wts = ops.fused_weights(jax.device_put(frames, cpu), cfg=cfg,
                                edge_threshold=hcfg.edge_threshold,
                                impl="xla")
        cxy, cw = ops.compact_raster(wts, width=w, max_edges=4096)
        dev = tpu if impl == "pallas" else cpu
        return ops.hough_vote(jax.device_put(cxy, dev),
                              jax.device_put(cw, dev), trig,
                              n_rho=rho_bins(h, w, hcfg), impl=impl)

    bad = []
    for name, fn in (("conv2d_gemm (Gauss, Sobel)", conv),
                     ("fused_weights (no corridors)", weights(None)),
                     ("fused_weights (4 corridors)", weights(corridors))):
        chip, ref = both(fn)
        n_diff = int((chip != ref).sum())
        print(f"  kernel {name}: {n_diff} of {ref.size} values differ "
              f"from the CPU reference")
        if n_diff:
            bad.append(name)
    chip, ref = both(vote)
    d = chip - ref                       # (N, n_rho, n_theta)
    moved = np.abs(d).sum() / 2
    # Earth mover's distance along rho: equal to ``moved`` exactly when
    # every differing vote moved by one rho bin within its theta column.
    emd = np.abs(np.cumsum(d, axis=1)).sum()
    print(f"  kernel hough_vote (180 bins): {moved:.0f} of {ref.sum():.0f} "
          f"votes one rho bin from the CPU reference's, "
          f"{emd - moved:.0f} bins of further movement")
    if emd != moved or np.abs(d.sum(axis=1)).sum():
        bad.append("hough_vote (180 bins)")
    return bad


def served_kernels(jax, svc, shapes):
    """{(shape, binding): kernel names} of the compiled served programs,
    read from each program's ``tpu_custom_call`` ops."""
    import re

    import numpy as np
    from repro.core.hough import full_corridors
    from repro.core.plan import _detect

    found = {}
    for shape in shapes:
        plan = svc.grids[shape].plan
        imgs = svc.plans.put(np.zeros((svc.batch_size,) + shape,
                                      np.float32))
        bins = svc.plans.put(np.arange(svc.gate_band, dtype=np.int32))
        cors = svc.plans.put(full_corridors(svc.fused_corridors))
        gated = plan.with_theta_band(svc.gate_band)
        for name, p, tb, cr in (
            ("full", plan, None, None),
            ("gated", gated, bins, None),
            ("fused", gated.with_fused(svc.fused_corridors), bins, cors),
        ):
            text = _detect.lower(p.cfg, imgs, tb, cr,
                                 tiers=p.tiers).compile().as_text()
            calls = [ln for ln in text.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in ln]
            found[shape, name] = sorted({
                m.group(1) for ln in calls
                for m in [re.search(r"jit\((\w+)\)/pallas_call", ln)] if m
            })
    return found


def single_chip(jax, seed: int) -> None:
    from repro.data import standard_drive_cycle
    from repro.data.scenarios import get_family
    from repro.serve.detection import DetectionService

    compiles = count_compiles(jax)
    print("kernels on the chip vs the jnp reference on the host:")
    bad_kernels = kernel_checks(jax, seed)
    kw = service_kwargs()
    chip = DetectionService(service_config(), **kw)

    t0 = time.perf_counter()
    chip.warm_up()
    warm = phase_one_frames(seed + 1)
    for shape in SHAPES:
        batch = [f for f in warm if f[2].shape == shape][: chip.batch_size]
        run_sessionless(chip, batch, deadline_s=None)
    run_session(chip, standard_drive_cycle("converging", 12, *SHAPES[0],
                                           seed=seed + 1),
                "warm-up", deadline_s=None, uid0=10_000)
    kernels = served_kernels(jax, chip, SHAPES)
    warm_s = time.perf_counter() - t0
    n_warm = sum(compiles.values())
    print(f"warm-up: {n_warm} programs built in {warm_s:.1f} s "
          f"(host clock; compile-dominated)")
    for (shape, name), names in sorted(kernels.items()):
        print(f"  served program {shape[0]}x{shape[1]} {name}: "
              f"tpu_custom_call kernels {names}")
        need = {"hough_vote"} | (
            {"fused_weights"} if name == "fused" else {"conv2d_gemm"}
        )
        if not need <= set(names):
            fail(f"{shape} {name}: missing chip kernels "
                 f"{sorted(need - set(names))}")

    before = dict(compiles)
    frames = phase_one_frames(seed)
    t1 = time.perf_counter()
    p1 = run_sessionless(chip, frames, deadline_s=DEADLINE_S)
    t2 = time.perf_counter()
    print(f"phase one: {len(p1)} sessionless requests in {t2 - t1:.3f} s "
          f"(host clock)")
    require_served(p1, "phase one")

    cycle = standard_drive_cycle("straight", N_CYCLE, *SHAPES[0], seed=seed)
    g0, f0 = chip.gated_dispatches, chip.fused_dispatches
    t3 = time.perf_counter()
    p2 = run_session(chip, cycle, "ego", deadline_s=DEADLINE_S, uid0=1000)
    t4 = time.perf_counter()
    gated = chip.gated_dispatches - g0
    fused = chip.fused_dispatches - f0
    print(f"phase two: {len(p2)}-frame session in {t4 - t3:.3f} s "
          f"(host clock); gated dispatches {gated}, fused dispatches "
          f"{fused}")
    require_served(p2, "phase two")
    late = sorted(k for k in compiles if compiles[k] > before.get(k, 0))
    print(f"compiles after warm-up: {sum(compiles.values()) - n_warm} "
          f"{late}")
    chip.close()
    if gated == 0 or fused == 0:
        fail("phase two never dispatched a gated and a fused plan")
    if late:
        fail(f"programs compiled after warm-up: {late}")

    cpu = jax.devices("cpu")[0]
    ref = DetectionService(service_config("xla"), device=cpu, **kw)
    r1 = run_sessionless(ref, frames, deadline_s=None)
    r2 = run_session(ref, cycle, "ego", deadline_s=None, uid0=1000)
    ref.close()
    require_served(r1 + r2, "CPU reference")

    n_theta = chip.cfg.hough.n_theta
    same, near, n = compare(p1 + p2, r1 + r2,
                            rho_bin=chip.cfg.hough.rho_res,
                            theta_bin=math.pi / n_theta + 1e-6)
    print(f"agreement with the CPU reference: {same}/{n} requests with "
          f"identical valid peaks, {near} more within one rho and one "
          f"theta bin, {n - same - near} further apart")
    if same + near != n:
        fail(f"{n - same - near} requests disagree with the CPU reference "
             f"by more than one bin")

    fams = [f for _, f, _, _ in frames] + ["straight"] * len(p2)
    truths = [t for _, _, _, t in frames] + list(cycle.truths())
    f1_chip = family_f1(p1 + p2, fams, truths)
    f1_ref = family_f1(r1 + r2, fams, truths)
    print("per-family F1 (chip / CPU reference / floor):")
    bad = []
    for fam in sorted(f1_chip):
        floor = get_family(fam).f1_floor
        c, r = f1_chip[fam], f1_ref[fam]
        ok = c >= r and (c >= floor or r < floor)
        print(f"  {fam:16s} {c:.4f} / {r:.4f} / {floor:.2f}"
              f"{'' if ok else '  <-- FAIL'}")
        if not ok:
            bad.append(fam)
    if bad:
        fail(f"F1 on the chip below the reference or the floor: {bad}")
    if bad_kernels:
        fail(f"kernels differ from the reference: {bad_kernels}")


def four_chips(jax, seed: int) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--chips 4 needs 4 chips, JAX sees {len(devices)}")
    kw = service_kwargs()
    kw.update(gate_band=None, fused_corridors=None)
    frames = [f for f in phase_one_frames(seed) if f[2].shape == SHAPES[1]]
    print(f"router phase: {len(frames)} sessionless "
          f"{SHAPES[1][0]}x{SHAPES[1][1]} requests")
    router_phase(devices, frames, service_config(), kw)


def router_phase(devices, frames, cfg, kw) -> None:
    """Serve ``frames`` through one replica per device and through one
    replica on the first device; every replica must sit on its own device
    and dispatch work, and the two fleets must agree exactly."""
    from repro.serve.fleet import ShardedDetectionService

    def serve(devs):
        fleet = ShardedDetectionService(cfg, n_replicas=len(devs),
                                        devices=devs, **kw)
        t0 = time.perf_counter()
        reqs = run_sessionless(fleet, frames, deadline_s=None)
        dt = time.perf_counter() - t0
        served = [(rep.service.device, rep.service.dispatches)
                  for rep in fleet.replicas]
        fleet.close()
        require_served(reqs, f"{len(devs)} replica(s)")
        print(f"  {len(devs)} replica(s): {len(reqs)} requests in "
              f"{dt:.3f} s (host clock, compiles included)")
        return reqs, served

    many, served = serve(list(devices))
    one, _ = serve([devices[0]])
    print(f"  dispatches per replica: "
          f"{[(str(d), n) for d, n in served]}")
    if (len({d for d, _ in served}) != len(devices)
            or not all(n > 0 for _, n in served)):
        fail(f"the {len(devices)} replicas did not each serve from a "
             f"device of their own")
    same, _, n = compare(many, one, rho_bin=0.0, theta_bin=0.0)
    print(f"agreement {len(devices)} replicas vs 1 replica: {same}/{n} "
          f"requests with identical valid peaks")
    if same != n:
        fail(f"{n - same} requests differ between {len(devices)} replicas "
             f"and 1")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    jax, cache_dir = setup()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}; jax {jax.__version__}")
    if args.chips == 4:
        four_chips(jax, args.seed)
    else:
        single_chip(jax, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
