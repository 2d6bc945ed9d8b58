"""The four-chip smoke run's router checks, on four host devices.

``chip_smoke.py --chips 4`` needs a 2x2 TPU host, but its checks do not:
``router_phase`` runs here on four forced CPU devices at a small bucket.
It must pass when every replica sits on its own device and dispatches
work, and fail when two replicas share a device or a replica serves
nothing.  The device count is fixed at JAX's first start, so the cases
run in one subprocess with ``XLA_FLAGS`` set, as in ``test_mesh.py``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import contextlib, io, json, sys
    sys.path[:0] = [REPO]
    import jax
    import chip_smoke
    from repro.core import HoughConfig, PipelineConfig
    from repro.data import make_scenario

    devs = jax.devices()
    assert len(devs) == 4, devs
    cfg = PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto"))
    kw = dict(buckets=((96, 128),), batch_size=2, prefetch=False)
    frames = [(i, "straight", make_scenario("straight", 96, 128,
                                            seed=i).image, None)
              for i in range(8)]
    cases = {
        "distinct": (devs, frames),
        "shared_device": ([devs[0], devs[0], devs[1], devs[2]], frames),
        "idle_replica": (devs, frames[:2]),
    }
    for name, (case_devs, case_frames) in cases.items():
        out, err = io.StringIO(), io.StringIO()
        code = 0
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            try:
                chip_smoke.router_phase(case_devs, case_frames, cfg, kw)
            except SystemExit as e:
                code = e.code
        print(json.dumps({"case": name, "code": code,
                          "out": out.getvalue(), "err": err.getvalue()}))
""")


@pytest.fixture(scope="module")
def router_cases():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", f"REPO = {REPO!r}\n" + SCRIPT],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return {c["case"]: c for c in map(json.loads, r.stdout.splitlines())}


def test_router_phase_passes_with_one_busy_replica_per_device(router_cases):
    case = router_cases["distinct"]
    assert case["code"] == 0, case
    assert "agreement 4 replicas vs 1 replica: 8/8" in case["out"]


@pytest.mark.parametrize("name", ["shared_device", "idle_replica"])
def test_router_phase_fails_without_one_busy_replica_per_device(
        router_cases, name):
    case = router_cases[name]
    assert case["code"] == 1, case
    assert "did not each serve from a device of their own" in case["err"]
