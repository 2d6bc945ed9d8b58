"""The reduction of the program's own records: ``service.*`` spans and the
host time they hold, idle gaps named by the innermost span around them,
device time by named scope through the compiled programs' op metadata,
and the readers over stamps and counters; on hand-built planes, on the
CPU at the tiny size, and on a trace recorded on a TPU v5e."""

import argparse
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chip_bench import program_run, program_trace as pt, registry
from chip_bench import trace_reduce

HLO_A = """\
HloModule jit__detect, is_scheduled=true

%fused_computation.1 (param_0: s32[8]) -> f32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %scatter.1 = f32[8]{0} scatter(%param_0), metadata={op_name="jit(_detect)/cond/branch_0_fun/compact/scatter"}
}

ENTRY %main.9 (Arg_0.1: f32[2,8]) -> f32[8] {
  %Arg_0.1 = f32[2,8]{1,0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(s32[8]{0} %Arg_0.1), kind=kCustom, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_detect)/canny/jit(conv2d_gemm)/mul;canny/add"}
  ROOT %custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_detect)/jit(_hough_transform)/vote/pallas_call"}
}
"""
# a second program whose %fusion.2 differs in shape and scope
HLO_B = """\
ENTRY %main.3 (Arg_0.1: f32[4,8]) -> f32[4] {
  %Arg_0.1 = f32[4,8]{1,0} parameter(0)
  ROOT %fusion.2 = f32[4]{0} fusion(f32[4,8]{1,0} %Arg_0.1), kind=kLoop, calls=%c, metadata={op_name="jit(_detect)/get_lines/reduce_max"}
}
"""


def test_scope_of_an_op_name_is_its_innermost_known_scope():
    assert pt.scope_of_op_name("jit(_detect)/canny/jit(x)/mul") == "canny"
    assert pt.scope_of_op_name(
        "jit(_detect)/vote/x/compact/scatter") == "compact"
    assert pt.scope_of_op_name("reduce_max;jit(_detect)/get_lines/y") == \
        "get_lines"
    assert pt.scope_of_op_name("jit(_detect)/hough_vote/cond") is None


def test_scope_table_falls_back_to_the_called_computation():
    t = pt.scope_table([HLO_A, HLO_B])
    assert pt.op_scope("%fusion.1 = f32[8]{0} fusion(s32[8]{0} %Arg_0.1),"
                       " kind=kCustom, calls=%fused_computation.1",
                       t) == "compact"
    assert pt.op_scope("%custom-call.3 = f32[8]{0} custom-call(...)",
                       t) == "vote"
    # one name in two programs: the instruction text decides
    assert pt.op_scope("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1)",
                       t) == "canny"
    assert pt.op_scope("%fusion.2 = f32[4]{0} fusion(f32[4,8]{1,0} "
                       "%Arg_0.1)", t) == "get_lines"
    assert pt.op_scope("%fusion.9 = f32[4]{0} fusion()", t) is None


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


span = ev


def profile():
    """A window of 1000 ns.  The service thread: a step [100, 500) with
    admit, plan and complete (block, then split) inside, a submit at
    600, a step [700, 900) with nothing inside (a poll).  The worker
    stages at [50, 150).  The device runs detection programs at
    [100, 150) and [300, 380), a slice program at [440, 460), and idles
    in between."""
    service = NS(name="python3", events=[
        span("bench.window", 0, 1000),
        span("bench.step", 100, 400),
        span("service.admit", 110, 60, dispatch=0),
        span("service.stage_wait", 120, 30, uid=1),
        span("service.plan", 180, 10, dispatch=0),
        span("service.complete", 200, 280, dispatch=0, uids="[1]"),
        span("service.block", 205, 95, dispatch=0),
        span("service.split", 380, 90, uid=1),
        span("bench.submit", 600, 20),
        span("bench.step", 700, 200),
    ])
    worker = NS(name="python3", events=[span("service.stage", 50, 100,
                                             uid=1)])
    host = NS(name="/host:CPU", lines=[service, worker])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__detect(11)", 100, 50), ev("jit__detect(11)", 300, 80),
            ev("jit_dynamic_slice(5)", 440, 20)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = f32[8]{0} fusion(s32[8]{0} %Arg_0.1)", 100, 30),
            ev("%custom-call.3 = f32[8]{0} custom-call()", 130, 20),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1)", 300, 60),
            ev("%fusion.9 = f32[8]{0} fusion()", 360, 20),
            # a slice program's op of a name the table knows: not counted
            ev("%fusion.1 = f32[1]{0} fusion(f32[8]{0} %x)", 440, 20),
        ]),
    ])
    return NS(planes=[host, dev])


def test_spans_host_time_and_named_idle_gaps():
    r = pt.reduce_program(profile(), pt.scope_table([HLO_A, HLO_B]))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["spans"]["service.stage"] == [1, pytest.approx(100e-9)]
    assert r["spans"]["service.split"] == [1, pytest.approx(90e-9)]
    # top level: admit 60 + plan 10 + complete 280, less stage_wait 30
    # and block 95
    assert r["host_s"] == pytest.approx(225e-9)
    # gaps: [0,100) generator, [150,300) mid 225 in block, [380,440)
    # mid 410 in split, [460,1000) mid 730 in the polling step
    assert r["idle_by_path"] == {
        "generator": pytest.approx(100e-9),
        "step>service.block": pytest.approx(150e-9),
        "step>service.split": pytest.approx(60e-9),
        "step": pytest.approx(540e-9)}
    assert r["idle_gaps"][0] == ["step", pytest.approx(540e-9)]
    assert r["scopes"] == {"compact": pytest.approx(30e-9),
                           "vote": pytest.approx(20e-9),
                           "canny": pytest.approx(60e-9),
                           "unscoped": pytest.approx(20e-9)}
    assert r["detect_s"] == pytest.approx(130e-9)


def test_without_a_window_or_a_device_the_trace_is_refused():
    p = profile()
    with pytest.raises(ValueError):
        pt.reduce_program(NS(planes=p.planes[1:]))
    with pytest.raises(ValueError):
        pt.reduce_program(NS(planes=p.planes[:1]))


def req(ok=True, **stamps):
    return NS(req=NS(ok=ok, **stamps))


def test_readers_over_stamps_counters_and_spans():
    reqs = [req(submitted_at=0.0, admitted_at=0.001 * i,
                dispatched_at=0.01 * i + 0.001 * i,
                finished_at=0.05 + 0.011 * i) for i in range(1, 101)]
    reqs.append(req(ok=False, submitted_at=0.0, admitted_at=9.0,
                    dispatched_at=9.0, finished_at=9.0))
    run = {"requests": reqs, "answered_in_window": 100,
           "counters": {"edge_pixels": 300, "vote_slots": 1200},
           "program": {"host_s": 0.2, "spans": {"service.admit": [1, 0.1]},
                       "scopes": {"compact": 0.15}}}
    read = {name: registry.metric_reader(name)(run) for name in
            program_run.METRICS["open_streams"]}
    assert read["admit_wait_p99_ms"] == pytest.approx(99.0)
    assert read["fill_wait_p50_ms"] == pytest.approx(500.0)
    assert read["answer_wait_p50_ms"] == pytest.approx(50.0)
    assert read["host_ms_per_frame.tracked"] == pytest.approx(2.0)
    assert read["vote_slot_use_pct.tracked"] == pytest.approx(25.0)
    assert read["compaction_ms_per_frame.tracked"] == pytest.approx(1.5)


def test_readers_read_nothing_from_a_program_without_the_records():
    """A program without stamps, counters, spans or scopes: every new
    reader returns None and none raises."""
    run = {"requests": [req(submitted_at=0.0, finished_at=0.1)],
           "answered_in_window": 1,
           "counters": {"dispatches": 3},
           "program": {"host_s": 0.0, "spans": {}, "scopes": {}}}
    for names in program_run.METRICS.values():
        for name in names:
            assert registry.metric_reader(name)(run) is None, name


@pytest.mark.parametrize("cell", ["tiny.tracked", "tiny.offline"])
def test_untraced_cell_reports_the_program_records(bench_copy, cell):
    args = argparse.Namespace(workload=cell, seed=2**31 + 41, seconds=1.5,
                              trace=0, keep=None)
    res = program_run.run_cell(args, root=bench_copy, require_tpu=False)
    kind = "open_streams" if cell.endswith("tracked") else "closed_loop"
    untraced = [n for n in program_run.METRICS[kind]
                if not n.startswith(("host_ms", "compaction_ms"))]
    for name in untraced:
        assert res["metrics"][name] > 0.0, name
    c = res["counters"]
    assert 0 < c["edge_pixels"] <= c["vote_slots"]
    if kind == "open_streams":
        m = res["metrics"]
        assert m["fill_wait_p50_ms"] <= m["latency_p50_ms"] + 1e-6


def test_program_texts_hold_every_scope(bench_copy):
    """The scope table of the tiny cell's compiled programs (the CPU's)
    names the four stages a staged dispatch runs."""
    from chip_bench import harness, registry as reg

    cell = reg.find_cell("tiny.offline", bench_copy)
    harness.prepare_environment(bench_copy)
    import jax

    svc = harness.build_service(cell["config"], jax.devices())
    svc.detect_many([cell_frame(cell)] * svc.batch_size)
    texts = program_run.program_texts(svc)
    svc.close()
    assert len(texts) == 1
    scopes = {s for cands in pt.scope_table(texts).values()
              for _, s in cands}
    assert scopes >= {"canny", "compact", "vote", "get_lines"}


def cell_frame(cell):
    import numpy as np

    f = cell["config"]["frame"]
    return np.zeros((f["height"], f["width"]), np.uint8)


FIXTURES = Path(__file__).resolve().parent / "fixtures"


def recorded(name):
    from jax.profiler import ProfileData

    raw = gzip.decompress((FIXTURES / name).read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def test_recorded_v5e_program_trace():
    """A third of a second of vga-caltech.offline recorded on one v5e
    (eight batch-4 dispatches at 480x640) with the program's spans and
    scopes, and the scope table of its compiled programs (the entries of
    the ops the trace holds)."""
    pd = recorded("v5e_offline_program_trace.xplane.pb.gz")
    table = json.loads(gzip.decompress(
        (FIXTURES / "v5e_offline_program_scopes.json.gz").read_bytes()))
    r = pt.reduce_program(pd, table)
    assert set(r["spans"]) >= {
        "service.admit", "service.stage", "service.stage_wait",
        "service.plan", "service.put", "service.launch",
        "service.complete", "service.block", "service.split"}
    assert r["spans"]["service.split"][0] == 32
    assert r["spans"]["service.launch"][0] == 8
    assert r["scopes"]["compact"] > 0.0
    scoped = sum(s for k, s in r["scopes"].items() if k in pt.SCOPES)
    assert scoped >= 0.9 * r["detect_s"]
    # the device idles while the host splits results, under program spans
    idle = sum(r["idle_by_path"].values())
    assert r["idle_by_path"]["step>service.split"] > 0.5 * idle
    assert sum(s for p, s in r["idle_by_path"].items()
               if p.startswith("step>service.")) > 0.9 * idle
    # the reductions agree: the same gaps, split further by program spans
    old = trace_reduce.reduce_profile(pd)
    assert idle == pytest.approx(sum(old["idle_by_cause"].values()))
    assert r["window_s"] == pytest.approx(old["window_s"])


def test_without_program_spans_gaps_keep_their_names():
    """On the trace recorded before the program had spans, every gap is
    named as the harness names it."""
    pd = recorded("v5e_offline_trace.xplane.pb.gz")
    r = pt.reduce_program(pd)
    old = trace_reduce.reduce_profile(pd)
    assert r["spans"] == {} and r["scopes"] == {}
    assert set(r["idle_by_path"]) == set(old["idle_by_cause"])
    for cause, s in old["idle_by_cause"].items():
        assert r["idle_by_path"][cause] == pytest.approx(s)
