"""The trace reduction: busy union, idle share, per-kernel time and idle
gaps named by the host span around them, on hand-built planes and on a
trace recorded on a TPU v5e."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chip_bench import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_offline_trace"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 1000),
        ev("bench.step", 1100, 300),
        ev("bench.submit", 1500, 100),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("fusion.1", 900, 300, tf_op="jit(_detect)/canny"),   # clipped
            ev("custom-call.2", 1250, 150,
               tf_op="jit(hough_vote)/pallas_call"),
            ev("custom-call.3", 1300, 200,
               tf_op="jit(hough_vote)/pallas_call"),             # overlaps
            ev("fusion.4", 1900, 300, tf_op="get_lines"),           # clipped
        ]),
        NS(name="XLA Modules", events=[ev("jit__detect", 900, 2000)]),
    ])
    return NS(planes=[host, dev])


def test_busy_union_and_window():
    r = trace_reduce.reduce_profile(profile())
    assert r["window_s"] == pytest.approx(1000e-9)
    # [1000,1200] + [1250,1500] + [1900,2000] = 200 + 250 + 100
    assert r["busy_s"] == pytest.approx(550e-9)
    assert r["n_devices"] == 1


def test_kernel_time_sums_matching_ops():
    r = trace_reduce.reduce_profile(profile())
    assert trace_reduce.kernel_seconds(r, "hough_vote") == \
        pytest.approx(350e-9)
    assert trace_reduce.kernel_seconds(r, "canny") == pytest.approx(200e-9)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    r = trace_reduce.reduce_profile(profile())
    # gaps: [1200,1250] inside step, [1500,1900] half submit -> midpoint
    # 1700 is outside every span: the generator
    assert r["idle_gaps"][0] == ["generator", pytest.approx(400e-9)]
    assert r["idle_gaps"][1] == ["step", pytest.approx(50e-9)]


def four_chips():
    """The window of ``profile`` on four TPU planes: chip n runs one op
    of 100 * (n + 1) ns from 1100."""
    host = profile().planes[0]
    devs = [NS(name=f"/device:TPU:{n}", lines=[NS(name="XLA Ops", events=[
        ev(f"fusion.{n}", 1100, 100 * (n + 1))])]) for n in range(4)]
    return NS(planes=[host] + devs)


def test_only_the_planes_of_the_services_devices_are_reduced():
    r = trace_reduce.reduce_profile(four_chips(), device_ids=[1, 3])
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx((200e-9 + 400e-9) / 2)
    assert set(r["ops"]) == {"fusion.1", "fusion.3"}
    # the first held chip's gaps: [1000,1100] in no span, [1300,2000]
    assert [s for _, s in r["idle_gaps"]] == [pytest.approx(700e-9),
                                              pytest.approx(100e-9)]
    every = trace_reduce.reduce_profile(four_chips())
    assert every["n_devices"] == 4
    assert every["busy_s"] == pytest.approx(250e-9)
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(four_chips(), device_ids=[7])


def test_device_time_per_frame_sums_over_the_held_chips():
    from chip_bench import layers

    r = trace_reduce.reduce_profile(four_chips(), device_ids=[1, 3])
    run = {"trace": r, "answered_in_window": 2}
    # 600 ns of device time over both chips for 2 frames
    assert layers.device_ms_per_frame(run) == pytest.approx(300e-9 * 1e3)
    assert layers.device_idle_pct(run) == pytest.approx(70.0)


def test_union_and_gaps_helpers():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                         (5, 6)]


def test_trace_without_a_window_or_a_device_is_refused():
    p = profile()
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(NS(planes=p.planes[1:]))
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(NS(planes=p.planes[:1]))


def test_recorded_v5e_trace():
    """A quarter second of vga-caltech.offline recorded on one v5e
    (five batch-4 dispatches at 480x640)."""
    import gzip

    from jax.profiler import ProfileData

    from chip_bench import layers

    raw = gzip.decompress((FIXTURE.parent / (FIXTURE.name + ".xplane.pb.gz"))
                          .read_bytes())
    r = trace_reduce.reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.251888644, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.097275456, rel=1e-9)
    vote = trace_reduce.kernel_seconds(r, layers.KERNELS["hough_vote"])
    canny = trace_reduce.kernel_seconds(r, layers.KERNELS["canny"])
    assert vote == pytest.approx(0.047151018, rel=1e-6)
    assert canny == pytest.approx(0.004440701, rel=1e-6)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert set(r["idle_by_cause"]) <= {"step", "submit", "generator"}


def test_recorded_v5e_trace_on_its_one_chip():
    """Naming the one chip the service held reads the whole trace."""
    import gzip

    from jax.profiler import ProfileData

    raw = gzip.decompress((FIXTURE.parent / (FIXTURE.name + ".xplane.pb.gz"))
                          .read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    assert trace_reduce.reduce_profile(pd, device_ids=[0]) == \
        trace_reduce.reduce_profile(pd)
