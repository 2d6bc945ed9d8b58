"""The end-to-end arithmetic: latency from the due time, unanswered
frames ranked above answered ones, goodput over the whole window."""

from chip_bench import latency


def frame(due, answered, done=True, deadline=None, sent=None):
    return latency.Frame(due=due, sent=due if sent is None else sent,
                         answered=answered, done=done, deadline=deadline)


def test_latency_runs_from_due_time_not_send_time():
    f = [frame(1.0, 1.25, sent=1.2)]
    assert abs(latency.percentile_s(f, 50, end=9.0) - 0.25) < 1e-12


def test_nearest_rank_percentiles():
    f = [frame(0.0, 0.001 * (i + 1)) for i in range(100)]
    assert latency.percentile_s(f, 50, end=1.0) == f[49].answered
    assert latency.percentile_s(f, 99, end=1.0) == f[98].answered
    assert latency.nearest_rank(1, 99) == 0


def test_unanswered_frames_rank_above_every_answered_frame():
    # An unanswered frame due late in the run waits less to the end than
    # a slow answered frame did, yet it ranks above it.
    f = [frame(0.0, 0.5), frame(0.0, 0.01), frame(9.9, None, done=False)]
    assert latency.percentile_s(f, 99, end=10.0) == 10.0 - 9.9
    assert latency.percentile_s(f, 50, end=10.0) == 0.5


def test_failed_counts_refused_degraded_and_late_frames():
    f = [frame(0.0, 0.1, deadline=0.3),             # in time
         frame(0.0, 0.4, deadline=0.3),             # late
         frame(0.0, 0.1, done=False, deadline=0.3),  # degraded
         frame(0.0, None, done=False, deadline=0.3)]  # refused
    assert latency.failed(f) == 3


def test_goodput_counts_full_answers_inside_the_window():
    f = [frame(-0.5, 0.2),          # sent before, answered inside: counts
         frame(0.1, 0.3),
         frame(0.2, 0.4, done=False),  # degraded: no
         frame(1.5, 2.5)]          # answered after the window: no
    assert latency.goodput_fps(f, 0.0, 2.0) == 2 / 2.0
