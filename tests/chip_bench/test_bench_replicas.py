"""A configuration that deploys replicas behind the router, run through
``run.run_cell`` on forced host devices: four replicas under a closed
loop and two under tracked streams are correct, every replica of the
closed loop dispatches, the counters are the sums of the replicas' own,
every answered frame's dispatch is recorded, and ``device.count`` is the
cell's chips.  A cell whose chips are not the configuration's replicas,
or a host with fewer devices, exits non-zero and prints no result.  Eight
frames in flight on four replicas of batch four never dispatch, a fault
of the router that PERF.md lists as Open question 11.
The device count is fixed at JAX's first start, so the cases run in one
subprocess with ``XLA_FLAGS`` set, as in ``tests/test_chip_smoke.py``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import argparse, contextlib, io, json, sys, time
    from pathlib import Path
    sys.path.insert(0, COPY)
    from chip_bench import harness, registry, run
    from chip_bench.traffic import generator

    root = Path(COPY)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    x8 = json.loads((root / "chip_bench/configs/tiny-x4.json").read_text())
    x8["replicas"] = 8
    (root / "chip_bench/configs/tiny-x8.json").write_text(json.dumps(x8))
    bench["configs"].append({"name": "tiny-x8", "source": "a test size",
                             "file": "chip_bench/configs/tiny-x8.json",
                             "reduced": ["frame"], "why": "tests"})
    bench["workloads"] += [
        {"name": "tiny-x4.one_chip", "config": "tiny-x4",
         "traffic": "tiny-k16", "chips": 1, "why": "tests"},
        {"name": "tiny-x2.four_chips", "config": "tiny-x2",
         "traffic": "tiny-s3", "chips": 4, "why": "tests"},
        {"name": "tiny-x4.closed_k8", "config": "tiny-x4",
         "traffic": "tiny-k8", "chips": 4, "why": "tests"},
        {"name": "tiny-x8.offline", "config": "tiny-x8",
         "traffic": "tiny-k16", "chips": 8, "why": "tests"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    seen = {}

    def keep(name, make):
        def made(*a, **kw):
            seen[name] = make(*a, **kw)
            return seen[name]
        return made

    harness.build_service = keep("svc", harness.build_service)
    harness.DispatchRecorder = keep("recorder", harness.DispatchRecorder)
    harness.Driver = keep("driver", harness.Driver)

    for cell in ("tiny-x4.offline", "tiny-x2.tracked"):
        args = argparse.Namespace(workload=cell, seed=2**31 + 123,
                                  seconds=1.5, trace=0, control=0)
        res = run.run_cell(args, require_tpu=False)
        svc, rec, drv = seen["svc"], seen["recorder"], seen["driver"]
        reps = harness.services(svc)
        print(json.dumps({
            "case": cell, "result": res,
            "replicas": {k: [getattr(s, k) for s in reps]
                         for k in harness.COUNTERS},
            "summed": harness.counters(svc),
            "router": {k: getattr(svc, k) for k in
                       ("dispatches", "gated_dispatches", "routed")},
            "warmed": [sorted(map(str, s._warmed)) for s in reps],
            "answered": [r.uid for r in drv.sent if r.req.ok],
            "recorded": sorted(rec.by_uid()),
            "recorded_replicas": sorted({d.replica for d in rec.log}),
        }))

    # the closed loop of eight, stepped for two seconds and no longer
    import jax
    cell = registry.find_cell("tiny-x4.closed_k8", root)
    svc = harness.build_service(cell["config"], jax.devices())
    drv = harness.Driver(svc, generator.build(cell["config"], cell["mix"],
                                              2**31 + 7))
    drv.run_until(time.perf_counter() + 2.0)
    print(json.dumps({"case": "tiny-x4.closed_k8", "sent": len(drv.sent),
                      "dispatches": [s.dispatches
                                     for s in harness.services(svc)]}))
    svc.close()

    harness.require_chip = lambda jax, chips: None
    for cell in ("tiny-x4.one_chip", "tiny-x2.four_chips",
                 "tiny-x8.offline"):
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            code = run.main(["--workload", cell, "--seed", "3000000001",
                             "--seconds", "1", "--trace", "0"])
        print(json.dumps({"case": cell, "code": code, "out": out.getvalue(),
                          "err": err.getvalue()}))
""")


@pytest.fixture(scope="module")
def cases(module_bench_copy):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    for var in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH"):
        env.pop(var, None)
    head = f"COPY = {str(module_bench_copy)!r}\n"
    r = subprocess.run(
        [sys.executable, "-c", head + SCRIPT],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return {c["case"]: c for c in map(json.loads, r.stdout.splitlines())}


@pytest.mark.parametrize("cell, chips", [("tiny-x4.offline", 4),
                                         ("tiny-x2.tracked", 2)])
def test_replica_cell_is_correct_on_the_cells_chips(cases, cell, chips):
    res = cases[cell]["result"]
    assert res["correct"], res["checks"]
    assert res["checks"]["peak_mismatch_pct"]["value"] == 0.0
    assert res["device"]["count"] == chips
    assert res["compiles_in_window"] == 0


def test_every_replica_dispatches_under_the_closed_loop(cases):
    """The first sixteen frames find every replica unmeasured and spread
    over all four; in the window the router sends work by each replica's
    measured service time, so the window's shares vary with the host."""
    case = cases["tiny-x4.offline"]
    assert min(case["replicas"]["dispatches"]) >= 1, case["replicas"]
    assert case["recorded_replicas"] == [0, 1, 2, 3]
    window = case["result"]["counters"]["replica_dispatches"]
    assert len(window) == 4
    assert sum(window) == case["result"]["counters"]["dispatches"] > 0


@pytest.mark.parametrize("cell", ["tiny-x4.offline", "tiny-x2.tracked"])
def test_counters_are_sums_over_the_replicas(cases, cell):
    case = cases[cell]
    for name, each in case["replicas"].items():
        assert case["summed"][name] == sum(each), name
    # the router's own sums and count of what it routed agree
    for name in ("dispatches", "gated_dispatches", "routed"):
        assert case["summed"][name] == case["router"][name], name
    window = case["result"]["counters"]
    assert window["routed"] >= case["result"]["attempted"] > 0


@pytest.mark.parametrize("cell", ["tiny-x4.offline", "tiny-x2.tracked"])
def test_every_answered_frame_has_its_dispatch_recorded(cases, cell):
    case = cases[cell]
    assert case["answered"]
    assert set(case["answered"]) <= set(case["recorded"])


def test_tracked_warm_up_builds_every_binding_on_each_replica(cases):
    warmed = cases["tiny-x2.tracked"]["warmed"]
    assert len(warmed) == 2
    # the full sweep, the gated plan and its fused twin, on each device
    assert len(warmed[0]) >= 3 and warmed[0] == warmed[1]


@pytest.mark.parametrize("cell, says", [
    ("tiny-x4.one_chip", "the cell's chips (1) differ from the "
                         "configuration's replicas (4)"),
    ("tiny-x2.four_chips", "the cell's chips (4) differ from the "
                           "configuration's replicas (2)"),
    ("tiny-x8.offline", "more than the devices JAX sees (4)"),
])
def test_more_replicas_than_chips_or_devices_is_refused(cases, cell, says):
    case = cases[cell]
    assert case["code"] != 0
    assert not any(ln.startswith("{") for ln in case["out"].splitlines())
    assert says in case["err"]


@pytest.mark.xfail(strict=True, reason=(
    "PERF.md Open question 11: the router spreads eight sessionless "
    "frames two to each of four replicas of batch four, and no grid "
    "without deadlines dispatches until it is full"))
def test_closed_loop_of_eight_dispatches_on_four_replicas(cases):
    """A closed-loop client of the fleet with 8 frames in flight gets an
    answer: some replica dispatches within two seconds of stepping.  A
    program change that fixes the router makes this test pass, and the
    marker then goes."""
    case = cases["tiny-x4.closed_k8"]
    assert case["sent"] == 8, case
    assert sum(case["dispatches"]) > 0, case
