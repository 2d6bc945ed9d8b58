"""Configurations, mixes and per-layer metrics are found by name; a cell
or a metric is added by files and an entry alone."""

import json

import pytest

from chip_bench import latency, registry
from chip_bench.traffic import generator


def test_every_cell_finds_its_config_and_mix():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cell = registry.find_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["mix"]["kind"] in ("open_streams", "closed_loop")


def test_every_per_layer_metric_has_a_reader():
    bench = registry.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in registry.end_to_end_for(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = registry.per_layer_for(bench, w["name"])
        assert layers
        assert all(m["moves"] in e2e for m in layers)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        registry.find_cell("no-such-cell")


def test_a_cell_and_a_metric_added_as_files_only(bench_copy):
    cell = registry.find_cell("tiny.tracked", bench_copy)
    assert cell["config"]["frame"] == {"height": 120, "width": 160,
                                       "format": "uint8 grayscale"}
    # a new metric: a reader file and an entry
    (bench_copy / "chip_bench" / "metrics" / "sends.py").write_text(
        "def read(run):\n    return float(len(run['frames']))\n")
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "sends", "unit": "frames", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "latency_p50_ms", "workloads": ["tiny.tracked"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = registry.load_benchmark(bench_copy)
    names = [m["name"] for m in registry.per_layer_for(bench,
                                                       "tiny.tracked")]
    assert "sends" in names
    assert registry.metric_reader("sends", bench_copy)({"frames": [1, 2]}) \
        == 2.0


@pytest.mark.parametrize("cell", ["tiny.tracked", "tiny.offline"])
def test_traffic_is_a_function_of_the_seed(bench_copy, cell):
    c = registry.find_cell(cell, bench_copy)
    seed = 2**31 + 12345            # larger than 32 signed bits
    a = generator.build(c["config"], c["mix"], seed)
    b = generator.build(c["config"], c["mix"], seed)
    other = generator.build(c["config"], c["mix"], seed + 1)

    def frames(t):
        if t.streams:
            return [s.frame(k)[1] for s in t.streams for k in range(40)]
        return [t.pool_frame(i)[1] for i in range(40)]

    fa, fb, fo = frames(a), frames(b), frames(other)
    assert all((x == y).all() for x, y in zip(fa, fb))
    assert any((x != y).any() for x, y in zip(fa, fo))
    # every seed offers the same arrivals
    assert [s.phase_s for s in a.streams] == [s.phase_s for s in
                                              other.streams]


def test_streams_drive_every_family_in_turn():
    cycles = {"a": [0, 1, 2, 3], "b": [10, 11]}
    s = generator.Stream("cam0", ["b", "a"], cycles, 6, 0.01, 0.05)
    got = [s.frame(k) for k in range(13)]
    assert [f for _, f in got] == [10, 11, 10, 11, 10, 11,
                                   0, 1, 2, 3, 2, 1, 10]
    assert got[7][0] == ("a", 1)
    assert s.due(3) == pytest.approx(0.16)
    assert [generator.bounce(k, 3) for k in range(6)] == [0, 1, 2, 1, 0, 1]


def add_cell(root, name, config, mix_name, mix):
    """A cell added by a mix file and a workload entry alone."""
    (root / "chip_bench" / "mixes" / f"{mix_name}.json").write_text(
        json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": mix_name, "chips": 1,
                               "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return registry.find_cell(name, root)


def test_a_family_subset_and_synced_streams_as_files_only(bench_copy):
    dense = {"kind": "open_streams", "streams": 3, "phase": "synced",
             "families": ["rain", "glare"], "cycle_frames": 4,
             "segment_frames": 2, "warm_s": 0.5}
    cell = add_cell(bench_copy, "tiny.dense", "tiny", "tiny-dense", dense)
    t = generator.build(cell["config"], cell["mix"], 5)
    assert [s.phase_s for s in t.streams] == [0.0, 0.0, 0.0]
    assert {s.frame(k)[0][0] for s in t.streams for k in range(8)} == \
        {"rain", "glare"}
    offline = {"kind": "closed_loop", "in_flight": 4, "families": ["night"],
               "pool_frames": 3, "warm_s": 0.5}
    cell = add_cell(bench_copy, "tiny.night", "tiny", "tiny-night", offline)
    t = generator.build(cell["config"], cell["mix"], 5)
    assert len(t.pool) == 3 and t.in_flight == 4


@pytest.mark.parametrize("phase, want", [
    ("spread", [0.0, 0.5, 0.0, 0.25, 0.5, 0.75]),
    ("synced", [0.0] * 6),
    ([0.0, 0.25], [0.0, 0.25, 0.0, 0.25, 0.0, 0.25])])
def test_stream_phases_come_from_the_mix(phase, want):
    mix = {"kind": "open_streams", "streams": 2, "phase": phase,
           "families": ["straight"], "cycle_frames": 2,
           "segment_frames": 2, "warm_s": 0.5}
    config = {"frame": {"height": 24, "width": 32}, "fps": 20,
              "deadline_ms": 300}
    got = [s.phase_s for n in (None, 4)      # the mix's count, a sweep's
           for s in generator.build(config, mix, 1, streams=n).streams]
    assert got == pytest.approx([p / 20 for p in want])


@pytest.mark.parametrize("bad", [{"families": "marked", "phase": "late"},
                                 {"families": ["no-such-family"]}])
def test_unknown_families_and_phases_are_refused(bad):
    mix = {"kind": "open_streams", "streams": 2, "cycle_frames": 2,
           "segment_frames": 2, "warm_s": 0.5, **bad}
    config = {"frame": {"height": 24, "width": 32}, "fps": 20,
              "deadline_ms": 300}
    with pytest.raises(ValueError):
        generator.build(config, mix, 1)


def test_an_end_to_end_metric_added_by_an_entry_alone(bench_copy):
    bench = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({
        "name": "latency_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": ["tiny.tracked"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = registry.load_benchmark(bench_copy)
    names = [m["name"] for m in registry.end_to_end_for(bench,
                                                        "tiny.tracked")]
    assert "latency_p99_ms" in names
    frames = [latency.Frame(due=0.0, sent=0.0, answered=0.001 * (i + 1),
                            done=True, deadline=None) for i in range(100)]
    run = {"frames": frames, "all_frames": frames, "t0": 0.0, "t1": 1.0,
           "t_end": 1.0, "setup_s": 12.5}
    for name, want in [("latency_p99_ms", 99.0), ("latency_p50_ms", 50.0),
                       ("goodput_fps", 100.0), ("setup_s", 12.5)]:
        assert registry.metric_reader(name, bench_copy)(run) == \
            pytest.approx(want)
