"""Run every paper-table benchmark; print tables; write CSVs + JSON.

The summary dict is also written to ``BENCH_paper_tables.json`` so every
bench run is machine-readable (the throughput benchmark writes its own
``BENCH_lines.json`` — see ``benchmarks/lines_throughput.py``).  With
``--scenarios`` the detection-quality suite also runs and emits
``BENCH_scenarios.json`` (see ``benchmarks/scenario_suite.py``).

With ``--service`` the mixed-resolution detection-service benchmark runs
too and emits ``BENCH_service.json`` (see ``benchmarks/service_suite.py``).

With ``--tracking`` the temporal drive-cycle suite runs and emits
``BENCH_tracking.json`` (see ``benchmarks/tracking_suite.py``): tracked vs
per-frame F1 and the prediction-gated Hough steady-state speedup.

With ``--fleet`` the overload + fault-injection suite runs and emits
``BENCH_fleet.json`` (see ``benchmarks/fleet_suite.py``): degradation
ladder on/off at equal offered load, coast-only F1 floors, and the fault
matrix's all-terminal contract.

With ``--mesh`` the sharded-fleet suite runs and emits
``BENCH_mesh.json`` (see ``benchmarks/mesh_suite.py``): the 1 -> 8
replica scaling curve at equal offered load (8-replica throughput must
strictly exceed 1-replica), the session-affinity ablation, the
speculative local/remote offload race — on the rtt_s compat path and
through the seeded lossy ``NetworkModel`` (bit-exact compat, local
guarantee under 5%/leg loss, deterministic replay) — plus the elastic
4 -> 8 scale-up arm and the diurnal arrival ramp.

With ``--drive`` the closed-loop drive suite runs and emits
``BENCH_drive.json`` (see ``benchmarks/drive_suite.py``): cross-track
trajectory error for blind/per-frame/tracked arms per family plus the
service arm under forced overload (ladder on vs off), with per-family
floors, tracked<=per-frame on noisy families, and deterministic replay
as gates (``scripts/check_drive.py`` pins the committed baseline).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--scenarios]
    [--service] [--tracking] [--fleet] [--mesh] [--drive]
"""

from __future__ import annotations

import json
import sys

from repro.runtime.compile_cache import enable_compile_cache

from .common import stamp_json
from .paper_tables import (
    table1_full_pipeline,
    table2_elided,
    table3_stage_split,
    table6_core_paths,
    table7_projected,
    table7_speedup_matrix,
    table_fused_roofline,
)


def _stamp_file(path: str) -> None:
    """Merge this run's timestamp/commit into a suite's BENCH_*.json.

    The suites are standalone scripts that predate the stamp; re-writing
    their JSON here (rather than editing every suite) guarantees every
    BENCH file a ``run.py`` invocation produces carries its provenance —
    a checked-in number nobody can date cannot be re-baselined honestly.
    """
    import os
    if not os.path.exists(path):
        return
    with open(path) as f:
        payload = json.load(f)
    with open(path, "w") as f:
        json.dump(stamp_json(payload), f, indent=2, default=float)


def main() -> None:
    enable_compile_cache()
    quick = "--quick" in sys.argv
    summary = {}

    if "--scenarios" in sys.argv:
        import os

        from . import scenario_suite
        if os.path.exists("BENCH_scenarios.json"):
            os.remove("BENCH_scenarios.json")  # never score a stale run
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        try:
            scenario_suite.main()
        except SystemExit:
            # contract violation: the suite writes its JSON before exiting,
            # so record the failure in the summary, finish the paper
            # tables, and re-signal via this process's exit code below.
            pass
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_scenarios.json")
        if os.path.exists("BENCH_scenarios.json"):
            with open("BENCH_scenarios.json") as f:
                sc = json.load(f)
            summary["scenario_autotune_contract_ok"] = (
                sc["autotune_contract_ok"]
            )
            summary["scenario_min_f1"] = min(
                r["f1"] for r in sc["rows"] if r["scenario"] != "empty"
            )
        else:  # suite aborted before writing — treat as a failed contract
            summary["scenario_autotune_contract_ok"] = False

    if "--service" in sys.argv:
        from . import service_suite
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        import os
        service_ok = True
        try:
            service_suite.main()
        except SystemExit:
            # the suite writes its JSON before exiting (same contract as
            # --scenarios): read the real bars instead of guessing which
            # one failed
            service_ok = False
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_service.json")
        if os.path.exists("BENCH_service.json"):
            with open("BENCH_service.json") as f:
                sv = json.load(f)
            summary["service_mixed_ge_batch8"] = sv["mixed_ge_batch8"]
            summary["service_holds_batch8"] = sv["service_holds_batch8"]
            summary["service_speedup_vs_naive"] = sv["speedup_vs_naive"]
            # deadline regime: virtual-clock simulation, so these two are
            # exact (no host-noise tolerance needed)
            summary["service_deadline_slack_zero_miss"] = (
                sv["deadline_slack_zero_miss"]
            )
            summary["service_deadline_edf_le_fifo"] = (
                sv["deadline_edf_le_fifo"]
            )
            summary["service_deadline_miss_rate_tight"] = (
                sv["deadline_tight_edf"]["miss_rate"]
            )
        else:  # suite aborted before writing
            summary["service_mixed_ge_batch8"] = False
            summary["service_holds_batch8"] = False
            summary["service_deadline_slack_zero_miss"] = False
            summary["service_deadline_edf_le_fifo"] = False
            summary["service_deadline_miss_rate_tight"] = None
        summary["service_contract_ok"] = service_ok and (
            summary["service_mixed_ge_batch8"]
            and summary["service_holds_batch8"]
            and summary["service_deadline_slack_zero_miss"]
            and summary["service_deadline_edf_le_fifo"]
        )

    if "--tracking" in sys.argv:
        import os

        from . import tracking_suite
        if os.path.exists("BENCH_tracking.json"):
            os.remove("BENCH_tracking.json")  # never score a stale run
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        tracking_ok = True
        try:
            tracking_suite.main()
        except SystemExit:
            # the suite writes its JSON before exiting (same contract as
            # the other suites): read the real bars below
            tracking_ok = False
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_tracking.json")
        if os.path.exists("BENCH_tracking.json"):
            with open("BENCH_tracking.json") as f:
                tr = json.load(f)
            summary["tracking_tracked_ge_per_frame"] = (
                tr["tracked_ge_per_frame_on_noisy"]
            )
            summary["tracking_gated_speedup"] = tr["gated_speedup"]
            summary["tracking_gated_speedup_ok"] = tr["gated_speedup_ok"]
        else:  # suite aborted before writing
            summary["tracking_tracked_ge_per_frame"] = False
            summary["tracking_gated_speedup"] = None
            summary["tracking_gated_speedup_ok"] = False
        summary["tracking_contract_ok"] = tracking_ok and (
            summary["tracking_tracked_ge_per_frame"]
            and summary["tracking_gated_speedup_ok"]
        )

    if "--fleet" in sys.argv:
        import os

        from . import fleet_suite
        if os.path.exists("BENCH_fleet.json"):
            os.remove("BENCH_fleet.json")  # never score a stale run
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        fleet_ok = True
        try:
            fleet_suite.main()
        except SystemExit:
            # the suite writes its JSON before exiting (same contract as
            # the other suites): read the real gates below
            fleet_ok = False
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_fleet.json")
        if os.path.exists("BENCH_fleet.json"):
            with open("BENCH_fleet.json") as f:
                fl = json.load(f)
            summary["fleet_high_pri_miss_improves"] = (
                fl["gates"]["high_pri_miss_improves"]
            )
            summary["fleet_coast_zero_dispatch"] = (
                fl["gates"]["coast_zero_dispatch"]
            )
            summary["fleet_faults_all_terminal"] = (
                fl["gates"]["faults_all_terminal"]
            )
            summary["fleet_tier0_miss_ladder_on"] = (
                fl["overload"]["ladder_on"]["tier0"]["miss_rate"]
            )
            summary["fleet_tier0_miss_ladder_off"] = (
                fl["overload"]["ladder_off"]["tier0"]["miss_rate"]
            )
        else:  # suite aborted before writing
            summary["fleet_high_pri_miss_improves"] = False
            summary["fleet_coast_zero_dispatch"] = False
            summary["fleet_faults_all_terminal"] = False
            summary["fleet_tier0_miss_ladder_on"] = None
            summary["fleet_tier0_miss_ladder_off"] = None
        summary["fleet_contract_ok"] = fleet_ok and (
            summary["fleet_high_pri_miss_improves"]
            and summary["fleet_coast_zero_dispatch"]
            and summary["fleet_faults_all_terminal"]
        )

    if "--mesh" in sys.argv:
        import os

        from . import mesh_suite
        if os.path.exists("BENCH_mesh.json"):
            os.remove("BENCH_mesh.json")  # never score a stale run
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        mesh_ok = True
        try:
            mesh_suite.main()
        except SystemExit:
            mesh_ok = False
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_mesh.json")
        # every gate the suite publishes, surfaced 1:1 (mesh_<gate>);
        # the contract is their conjunction — a new suite gate tightens
        # the contract here with no further wiring
        mesh_gates = (
            "throughput_scales", "affinity_tier0_no_worse",
            "speculative_local_guarantee", "speculative_upgrade_iff_wins",
            "all_terminal", "network_compat_bitexact",
            "lossy_local_guarantee", "lossy_upgrade_iff_wins",
            "lossy_deterministic", "scaleup_throughput_no_worse",
            "diurnal_all_terminal",
        )
        if os.path.exists("BENCH_mesh.json"):
            with open("BENCH_mesh.json") as f:
                ms = json.load(f)
            for gate in mesh_gates:
                summary[f"mesh_{gate}"] = ms["gates"].get(gate, False)
            summary["mesh_throughput_1"] = (
                ms["scaling"]["1"]["throughput_rps"]
            )
            summary["mesh_throughput_8"] = (
                ms["scaling"]["8"]["throughput_rps"]
            )
            summary["mesh_lossy_timeout_rate"] = (
                ms["network"]["lossy"]["timeout_rate"]
            )
            summary["mesh_scaleup_throughput"] = (
                ms["scale_up"]["elastic_4_to_8"]["throughput_rps"]
            )
        else:  # suite aborted before writing
            for gate in mesh_gates:
                summary[f"mesh_{gate}"] = False
            summary["mesh_throughput_1"] = None
            summary["mesh_throughput_8"] = None
            summary["mesh_lossy_timeout_rate"] = None
            summary["mesh_scaleup_throughput"] = None
        summary["mesh_contract_ok"] = mesh_ok and all(
            summary[f"mesh_{gate}"] for gate in mesh_gates
        )

    if "--drive" in sys.argv:
        import os

        from . import drive_suite
        if os.path.exists("BENCH_drive.json"):
            os.remove("BENCH_drive.json")  # never score a stale run
        saved_argv = sys.argv
        sys.argv = [saved_argv[0]] + (["--quick"] if quick else [])
        drive_ok = True
        try:
            drive_suite.main()
        except SystemExit:
            # the suite writes its JSON before exiting (same contract as
            # the other suites): read the real gates below
            drive_ok = False
        finally:
            sys.argv = saved_argv
        _stamp_file("BENCH_drive.json")
        # every gate the suite publishes, surfaced 1:1 (drive_<gate>);
        # the contract is their conjunction plus the suite's own exit
        drive_gates = (
            "tracked_under_floor", "tracked_le_per_frame_on_noisy",
            "ladder_on_beats_off", "deterministic_replay",
        )
        if os.path.exists("BENCH_drive.json"):
            with open("BENCH_drive.json") as f:
                dr = json.load(f)
            for gate in drive_gates:
                summary[f"drive_{gate}"] = dr["gates"].get(gate, False)
            summary["drive_worst_tracked_max_m"] = max(
                arms["tracked"]["max_cross_track_m"]
                for arms in dr["families"].values()
            )
            summary["drive_ladder_on_mean_m"] = (
                dr["service"]["ladder_on"]["mean_cross_track_m"]
            )
            summary["drive_ladder_off_mean_m"] = (
                dr["service"]["ladder_off"]["mean_cross_track_m"]
            )
        else:  # suite aborted before writing
            for gate in drive_gates:
                summary[f"drive_{gate}"] = False
            summary["drive_worst_tracked_max_m"] = None
            summary["drive_ladder_on_mean_m"] = None
            summary["drive_ladder_off_mean_m"] = None
        summary["drive_contract_ok"] = drive_ok and all(
            summary[f"drive_{gate}"] for gate in drive_gates
        )

    t1 = table1_full_pipeline()
    t2 = table2_elided()
    summary["elision_speedup"] = t1["total_us"] / t2["total_us"]
    summary["render_share"] = t1["render_share"]

    t3 = table3_stage_split()
    summary["canny_share"] = t3["canny_share"]

    t6 = table6_core_paths()
    summary["t6_canny_speedup"] = t6["canny_speedup"]
    summary["t6_hough_speedup"] = t6["hough_speedup"]

    t7 = table7_speedup_matrix()
    summary["best_total_speedup"] = t7["best_total_speedup"]
    t7p = table7_projected()
    summary["projected_total_speedup"] = t7p["projected_total_speedup"]

    tf = table_fused_roofline()
    summary["fused_roofline_stages"] = tf["stages"]
    summary["fused_hot_path_bytes"] = tf["fused_hot_path_bytes"]
    summary["staged_hot_path_bytes"] = tf["staged_hot_path_bytes"]
    summary["fused_traffic_below_staged"] = (
        tf["fused_traffic_below_staged"]
    )

    print("\n== summary (paper claims -> this platform) ==")
    print("  [methodology: the host is a vector CPU with no matrix unit, "
          "so GEMM-offload wins appear in the TPU projection, not the "
          "host wall-clock — the mirror image of the paper's platform]")
    print(f"  image generation share (paper: 76% on 50MHz core): "
          f"{summary['render_share']:.0%} here (vectorized renderer)")
    print(f"  elision win (paper: 4.2x): {summary['elision_speedup']:.2f}x "
          f"here")
    print(f"  canny share of detection (paper: 87.6% scalar): "
          f"{summary['canny_share']:.0%} here (canny already vectorized; "
          f"the scatter-bound Hough dominates a CPU)")
    print(f"  projected total speedup, VPU-only vs MXU-offload on TPU v5e "
          f"(paper: 3.7x vs Rocket): "
          f"{summary['projected_total_speedup']:.2f}x")
    if "scenario_min_f1" in summary:
        ok = summary["scenario_autotune_contract_ok"]
        print(f"  scenario suite: min family F1 "
              f"{summary['scenario_min_f1']:.2f}, max_edges autotune "
              f"contract {'ok' if ok else 'VIOLATED'}")
    if "service_contract_ok" in summary:
        miss = summary.get("service_deadline_miss_rate_tight")
        miss_txt = (f"tight-EDF miss rate {miss:.0%}"
                    if miss is not None else "deadline regime missing")
        ok = summary["service_contract_ok"]
        print(f"  detection service: deadline regime (virtual clock) "
              f"{miss_txt}, QoS+throughput gates "
              f"{'ok' if ok else 'VIOLATED'}")
    if "tracking_contract_ok" in summary:
        sp = summary.get("tracking_gated_speedup")
        sp_txt = f"{sp:.2f}x" if sp is not None else "missing"
        ok = summary["tracking_contract_ok"]
        print(f"  temporal tracking: gated-Hough steady state {sp_txt} "
              f"(gate >= 1.5x), tracked>=per-frame on noisy cycles "
              f"{'ok' if ok else 'VIOLATED'}")
    if "fleet_contract_ok" in summary:
        on = summary.get("fleet_tier0_miss_ladder_on")
        off = summary.get("fleet_tier0_miss_ladder_off")
        miss_txt = (f"tier-0 miss {on:.1%} (ladder) vs {off:.1%} (off)"
                    if on is not None and off is not None
                    else "overload arms missing")
        ok = summary["fleet_contract_ok"]
        print(f"  fleet overload: {miss_txt}, coast/fault gates "
              f"{'ok' if ok else 'VIOLATED'}")
    if "mesh_contract_ok" in summary:
        t1 = summary.get("mesh_throughput_1")
        t8 = summary.get("mesh_throughput_8")
        thr_txt = (f"throughput {t1:.0f} -> {t8:.0f} rps (1 -> 8 "
                   f"replicas)" if t1 is not None and t8 is not None
                   else "scaling arms missing")
        ok = summary["mesh_contract_ok"]
        print(f"  sharded fleet: {thr_txt}, affinity/offload gates "
              f"{'ok' if ok else 'VIOLATED'}")

    if "drive_contract_ok" in summary:
        worst = summary.get("drive_worst_tracked_max_m")
        on = summary.get("drive_ladder_on_mean_m")
        off = summary.get("drive_ladder_off_mean_m")
        err_txt = (f"worst tracked max {worst:.2f} m, overload mean "
                   f"{on:.2f} m (ladder) vs {off:.2f} m (off)"
                   if worst is not None and on is not None
                   and off is not None else "arms missing")
        ok = summary["drive_contract_ok"]
        print(f"  closed-loop drive: {err_txt}, trajectory gates "
              f"{'ok' if ok else 'VIOLATED'}")

    gap = (summary["staged_hot_path_bytes"]
           / max(summary["fused_hot_path_bytes"], 1.0))
    print(f"  fused hot path HBM traffic: "
          f"{summary['fused_hot_path_bytes']:.2e} B vs staged "
          f"{summary['staged_hot_path_bytes']:.2e} B ({gap:.2f}x less; "
          f"gate {'ok' if summary['fused_traffic_below_staged'] else 'VIOLATED'})")

    path = "BENCH_paper_tables.json"
    with open(path, "w") as f:
        json.dump(stamp_json(summary), f, indent=2, default=float)
    print(f"\nwrote {path}")
    if not (summary.get("scenario_autotune_contract_ok", True)
            and summary.get("service_contract_ok", True)
            and summary.get("tracking_contract_ok", True)
            and summary.get("fleet_contract_ok", True)
            and summary.get("mesh_contract_ok", True)
            and summary.get("drive_contract_ok", True)
            and summary["fused_traffic_below_staged"]):
        raise SystemExit(1)  # CI gates on the exit code, not the JSON


if __name__ == "__main__":
    main()
