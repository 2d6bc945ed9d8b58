"""Video-rate line detection: the paper's deployment loop, batched + streamed.

The paper targets ~300 ms/frame at 50 MHz (a frame every 4 m at 50 km/h).
This runs the detector over a drifting synthetic stream through the
batched/streamed fast path — frames are staged into batches, dispatched as
one kernel launch each, and double-buffered so the host decodes batch k+1
while the device computes batch k — and reports frames/s plus the
heterogeneous placement plan the offload planner derives for this
resolution (the paper's core/accelerator split, computed not hand-chosen).

``--scenario`` picks any road-scene family from the scenario engine
(``--scenario mixed`` rotates through all of them — a heterogeneous
stream), detection quality is scored live against the planted ground truth,
and ``--auto-max-edges`` lets the edge-density estimator size the Hough
compaction buffer per batch.

``--deadline-ms`` switches the loop from the raw stream to the
deadline-aware ``DetectionService`` (``serve/detection.py``): every frame
becomes a request with that latency budget, the dispatcher schedules
earliest-deadline-first with early batch close, and the run reports the
miss/shed counts next to throughput — the paper's real-time contract made
observable.  ``--render-overlay`` asks for the per-request phase-3 overlay
on the final frame (the paper's elided image-generation phase, on demand).

``--track`` streams a *drive cycle* (``data/scenarios.py`` ego-motion
sequences) through the session-stateful service path instead: every frame
carries one ``session_id``, the per-session ``LaneTracker``
(``core/tracking.py``) smooths the lanes and coasts through dropout
frames, and the final frame is rendered with the smoothed tracks overlaid
— tracked vs per-frame F1 are reported side by side.

    PYTHONPATH=src python examples/video_pipeline.py --frames 16 --batch 4 \
        --scenario mixed --auto-max-edges --deadline-ms 500
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    HoughConfig, LineDetector, PipelineConfig, aggregate_scores,
    peak_segments, plan_line_detection, score_frame, tracks_as_peaks,
)
from repro.core.lines import render_lines
from repro.data import scenario_names, scenario_stream, standard_drive_cycle
from repro.runtime.compile_cache import enable_compile_cache


def serve_with_tracking(args, cfg: PipelineConfig) -> None:
    """Session-stateful streaming: every frame of a drive cycle rides one
    ``session_id`` through the DetectionService, the per-session
    LaneTracker smooths/coasts the lanes, and the final frame is rendered
    with the SMOOTHED tracks overlaid (the temporal layer made visible)."""
    from repro.serve.detection import DetectionRequest, DetectionService

    family = "converging" if args.scenario == "mixed" else args.scenario
    cyc = standard_drive_cycle(family, args.frames, args.height, args.width,
                               seed=2)
    shape = (args.height, args.width)
    svc = DetectionService(cfg, buckets=(shape,), batch_size=args.batch)
    svc.detect_many([np.zeros(shape, np.float32)] * args.batch)  # warm
    reqs = [DetectionRequest(uid=i, frame=f.scene.image, session_id="cam0")
            for i, f in enumerate(cyc)]
    t0 = time.time()
    for r in reqs:       # drip-feed: one arrival per engine step
        svc.submit(r)
        svc.step()
    svc.run()
    dt = time.time() - t0
    svc.close()
    per = aggregate_scores([
        score_frame(r.result.peaks, r.result.valid,
                    cyc.frames[r.uid].scene.lines_rho_theta)
        for r in reqs
    ])
    trk = aggregate_scores([
        score_frame(*tracks_as_peaks(r.tracks),
                    cyc.frames[r.uid].scene.lines_rho_theta)
        for r in reqs
    ])
    drops = sum(f.dropout for f in cyc)
    print(f"\n{len(reqs)} drive-cycle frames ({family}, {drops} dropout) "
          f"in {dt:.2f}s -> {len(reqs)/dt:.1f} frames/s through the "
          f"session-stateful service")
    print(f"detection quality: per-frame F1={per['f1']:.2f} vs "
          f"tracked F1={trk['f1']:.2f} "
          f"(smoothing + coasting through dropouts)")
    # overlay the final frame with the SMOOTHED track lines, through the
    # same endpoint convention get_lines uses for detections
    tracks = reqs[-1].tracks
    track_peaks, _ = tracks_as_peaks(tracks)
    lines = peak_segments(track_peaks[:, 0], track_peaks[:, 1],
                          half=float(max(shape)))
    rend = np.asarray(render_lines(
        jnp.asarray(cyc.frames[-1].scene.image),
        lines, jnp.ones(len(tracks), bool),
    ))
    print(f"final-frame overlay from {len(tracks)} smoothed tracks: "
          f"shape {rend.shape}, "
          f"{int((rend[..., 0] == 255).sum())} red line pixels")


def serve_with_deadlines(args, cfg: PipelineConfig) -> None:
    """Drive the stream through the deadline-aware DetectionService:
    per-request latency budgets, EDF dispatch with early batch close, and
    explicit miss accounting instead of silent tail latency."""
    from repro.serve.detection import DetectionRequest, DetectionService

    shape = (args.height, args.width)
    svc = DetectionService(cfg, buckets=(shape,), batch_size=args.batch)
    svc.detect_many([np.zeros(shape, np.float32)] * args.batch)  # warm
    if args.render_overlay:
        # warm the render-bound program too, or its compile lands inside
        # the timed loop and masquerades as a deadline miss
        warm = DetectionRequest(uid=-1, frame=np.zeros(shape, np.float32),
                                render_output=True)
        svc.submit(warm)
        svc.run()
    svc.dispatches = svc.completed = 0
    scenes = list(scenario_stream(args.scenario, args.frames,
                                  args.height, args.width, seed=2))
    reqs = [
        DetectionRequest(
            uid=i, frame=s.image, deadline_s=args.deadline_ms / 1e3,
            render_output=args.render_overlay and i == len(scenes) - 1,
        )
        for i, s in enumerate(scenes)
    ]
    t0 = time.time()
    for r in reqs:       # drip-feed: one arrival per engine step
        svc.submit(r)
        svc.step()
    svc.run()
    dt = time.time() - t0
    svc.close()
    answered = [r for r in reqs if r.ok]
    missed = sum(r.missed_deadline for r in reqs)
    lat = sorted(r.latency_s for r in answered)
    p99 = (f"p99 latency {1e3 * lat[int(0.99 * (len(lat) - 1))]:.1f} ms"
           if lat else "no requests answered")
    print(f"\n{len(reqs)} requests in {dt:.2f}s -> "
          f"{len(reqs)/dt:.1f} req/s at deadline {args.deadline_ms:.0f} ms; "
          f"answered {len(answered)}, shed {svc.shed_deadline}, "
          f"rejected {svc.rejected_queue_full}, late {svc.completed_late} "
          f"-> miss rate {missed/len(reqs):.0%}; {p99}")
    if answered:
        agg = aggregate_scores([
            score_frame(r.result.peaks, r.result.valid,
                        scenes[r.uid].lines_rho_theta)
            for r in answered
        ])
        print(f"detection quality (answered requests): "
              f"F1={agg['f1']:.2f} (P={agg['precision']:.2f} "
              f"R={agg['recall']:.2f})")
    if args.render_overlay and reqs[-1].ok:
        rend = np.asarray(reqs[-1].result.rendered)
        print(f"final-frame overlay: shape {rend.shape}, "
              f"{int((rend[..., 0] == 255).sum())} red line pixels "
              f"(per-request render_output)")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--height", type=int, default=240)
    ap.add_argument("--width", type=int, default=320)
    ap.add_argument("--batch", type=int, default=4,
                    help="frames per device dispatch (1 = unbatched)")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable the edge-compaction Hough fast path")
    ap.add_argument("--scenario", default="converging",
                    choices=sorted(scenario_names()) + ["mixed"],
                    help="road-scene family (mixed = rotate through all)")
    ap.add_argument("--auto-max-edges", action="store_true",
                    help="size the compaction buffer from the edge-density "
                         "estimate (HoughConfig(max_edges='auto'))")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="serve frames through the deadline-aware "
                         "DetectionService with this latency budget per "
                         "request (EDF + early batch close) and report the "
                         "miss rate")
    ap.add_argument("--render-overlay", action="store_true",
                    help="with --deadline-ms: request the rendered line "
                         "overlay for the final frame (per-request "
                         "render_output)")
    ap.add_argument("--track", action="store_true",
                    help="stream a drive cycle through the session-"
                         "stateful service path (session_id + per-session "
                         "LaneTracker) and overlay the smoothed tracks on "
                         "the final frame")
    args = ap.parse_args()
    if args.track and args.deadline_ms is not None:
        ap.error("--track demonstrates the session-stateful path; run it "
                 "without --deadline-ms")
    if args.render_overlay and args.deadline_ms is None:
        ap.error("--render-overlay demonstrates per-request render on the "
                 "service path; it needs --deadline-ms")
    if args.auto_max_edges and args.no_compact:
        ap.error("--auto-max-edges sizes the compaction buffer; "
                 "it needs compaction on (drop --no-compact)")

    print("offload plan (paper §4.4 partition, derived):")
    for p in plan_line_detection(args.height, args.width):
        print(f"  {p.stage:18s} -> {p.unit.upper():4s} ({p.reason})")

    cfg = PipelineConfig(
        hough=HoughConfig(
            compact=not args.no_compact,
            max_edges="auto" if args.auto_max_edges else None,
        )
    )
    if args.track:
        serve_with_tracking(args, cfg)
        return
    if args.deadline_ms is not None:
        serve_with_deadlines(args, cfg)
        return

    det = LineDetector(cfg)
    if args.auto_max_edges:
        from repro.core import max_edge_tiers
        from repro.kernels.ops import default_max_edges
        # No probe/pinning needed: the detector's plan resolves "auto" ON
        # THE DEVICE — each chunk's edge count picks a compaction tier
        # inside the compiled program (core/plan.py), so a mixed stream
        # never re-resolves or recompiles mid-flight.
        tiers = max_edge_tiers(args.height, args.width)
        print(f"device-side autotune tiers: max_edges in {tiers} "
              f"(hand-tuned default "
              f"{default_max_edges(args.height * args.width)})")

    # warmup / compile at the steady-state batch shape
    warm = [
        s.image
        for s in scenario_stream(args.scenario, args.batch,
                                 args.height, args.width)
    ]
    jax.block_until_ready(
        det.detect_batch(jnp.asarray(warm, jnp.float32)).lines
    )

    # Stream frames through; keep only the tiny (K, 2)/(K,) peak fields
    # per frame (not edges/images — memory stays O(frames * K), and the
    # host never syncs inside the timed window).  Scoring runs after.
    truths, peaks, valids = [], [], []

    def frames():
        for s in scenario_stream(args.scenario, args.frames,
                                 args.height, args.width, seed=2):
            truths.append(s.lines_rho_theta)
            yield s.image

    t0 = time.time()
    for res in det.detect_stream(frames(), batch_size=args.batch):
        peaks.append(res.peaks)
        valids.append(res.valid)
    jax.block_until_ready(peaks[-1])
    dt = time.time() - t0
    agg = aggregate_scores([
        score_frame(p, v, t) for p, v, t in zip(peaks, valids, truths)
    ])
    print(f"\n{args.frames} frames in {dt:.2f}s -> "
          f"{args.frames/dt:.1f} frames/s "
          f"({1000*dt/args.frames:.1f} ms/frame; paper target ~300 ms); "
          f"batch={args.batch}, compact={not args.no_compact}, "
          f"scenario={args.scenario}")
    print(f"detection quality vs planted ground truth: "
          f"F1={agg['f1']:.2f} (P={agg['precision']:.2f} "
          f"R={agg['recall']:.2f}), "
          f"rho err {agg['mean_rho_err']:.1f}px, "
          f"theta err {agg['mean_theta_err_deg']:.1f} deg")


if __name__ == "__main__":
    main()
