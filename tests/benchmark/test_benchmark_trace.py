"""benchmark/trace.py on a hand-made two-chip trace.

``fixtures/two_chips.xplane.pb`` is the serialised form of
``fixtures/two_chips.xspace.txt``. In nanoseconds:

    chip 0, XLA Ops:  while.1 [100,900] holding fusion.1 [100,300],
        all-reduce-start.1 [300,310], fusion.2 [310,500],
        all-reduce-done.1 [500,600], all-gather.1 [600,650],
        fusion.1 [650,900]; then copy.3 [950,1000]
    chip 0, Async XLA Ops:  all-reduce-start.1 [300,600]
    chip 1, XLA Ops:  fusion.1 [100,500], all-reduce.2 [500,700],
        fusion.2 [700,1000]

each event named, as a TPU trace names it, by its instruction's whole text;
fusion.1 is a kOutput fusion giving bf16[32,196,1024], fusion.2 a kLoop
fusion giving f32[32,16,196,196].
    host:  bench:window [0,1100], bench:train_pass [50,905],
        bench:set_epoch [905,945], bench:train_pass [945,1090]

So chip 0 is busy 800 + 50 = 850 and chip 1 900: mean 875 of 1100. Chip
0's collectives cover [300,650] = 350, of which fusion.2 hides 190, leaving
160 exposed; chip 1's one synchronous all-reduce is 200, all exposed: means
275 and 180. Chip 0 idles in [0,100], [900,950], [1000,1100] and chip 1 in
[0,100], [1000,1100]; [900,950] falls mostly under bench:set_epoch, the
rest under bench:train_pass: means 200 and 25.
"""

import os

import pytest

from benchmark import trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NS = 1e-9


@pytest.fixture(scope="module")
def planes():
    return trace.load(os.path.join(FIXTURES, "two_chips.xplane.pb"))


@pytest.fixture(scope="module")
def reduced(planes):
    return trace.reduce(planes)


def test_fixture_is_the_text_it_was_made_from(planes):
    with open(os.path.join(FIXTURES, "two_chips.xspace.txt")) as f:
        assert trace.load_text_proto(f.read()) == planes


def test_loader_keeps_the_op_lines_of_a_device(planes):
    by_name = {p.name: p for p in planes}
    assert [ln.name for ln in by_name["/device:TPU:0"].lines] == [
        "XLA Ops", "Async XLA Ops"]
    assert [ln.name for ln in by_name["/device:TPU:1"].lines] == ["XLA Ops"]
    ops = by_name["/device:TPU:0"].lines[0].events
    assert len(ops) == 8
    assert ops[1] == trace.Event("fusion.1", 100.0, 300.0,
                                 "fusion[kOutput] bf16[32,196,1024]")
    assert by_name["/host:CPU"].lines[0].events[0] == trace.Event(
        "bench:window", 0.0, 1100.0)


def test_window_is_the_benchmarks_span(reduced):
    assert reduced["window_source"] == "host_span"
    assert reduced["window_s"] == pytest.approx(1100 * NS)
    assert reduced["devices"] == 2


def test_busy_is_the_union_averaged_over_chips(reduced):
    assert reduced["busy_s"] == pytest.approx(875 * NS)
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(
        225 / 1100)


def test_collective_total_and_exposed(reduced):
    assert reduced["collective_s"] == pytest.approx(275 * NS)
    assert reduced["collective_exposed_s"] == pytest.approx(180 * NS)


def test_op_totals_are_self_times_by_group(reduced):
    ops = dict(reduced["device_ops"])
    # fusion.1: 200 + 250 on chip 0, 400 on chip 1; fusion.2: 190 and 300.
    assert ops["fusion[kOutput] bf16[32,196,1024]"] == pytest.approx(425 * NS)
    assert ops["fusion[kLoop] f32[32,16,196,196]"] == pytest.approx(245 * NS)
    assert ops["while s32[]"] == pytest.approx(0.0)
    assert ops["all-reduce-done f32[1024,4096]"] == pytest.approx(50 * NS)
    assert ops["all-reduce f32[1024,4096]"] == pytest.approx(100 * NS)
    assert reduced["device_ops"][0][0] == "fusion[kOutput] bf16[32,196,1024]"
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])


def test_idle_gaps_go_to_the_span_that_covers_them(reduced):
    assert dict(reduced["idle_gaps"]) == {
        "bench:train_pass": pytest.approx(200 * NS),
        "bench:set_epoch": pytest.approx(25 * NS)}
    assert reduced["longest_gap_s"] == pytest.approx(100 * NS)
    gaps = sum(v for _k, v in reduced["idle_gaps"])
    assert gaps + reduced["busy_s"] == pytest.approx(reduced["window_s"])


def test_window_falls_back_to_the_device_extent(planes):
    no_span = [p for p in planes if p.name != "/host:CPU"]
    got = trace.reduce(no_span)
    assert got["window_source"] == "device_extent"
    assert got["window_s"] == pytest.approx(900 * NS)
    assert got["busy_s"] == pytest.approx(875 * NS)
    assert got["idle_gaps"] == [["(no span)", pytest.approx(25 * NS)]]


def test_a_trace_without_device_ops_is_refused(planes):
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce([p for p in planes if p.name == "/host:CPU"])


def test_top_limits_both_lists(planes):
    got = trace.reduce(planes, top=1)
    assert len(got["device_ops"]) == 1 and len(got["idle_gaps"]) == 1


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 10)], [(-5, 1), (9, 20)], [(1, 9)]),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert trace.length(trace.union([(0, 2), (1, 3)])) == 3
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_self_times_nest_two_levels():
    ev = [trace.Event("outer", 0, 100), trace.Event("mid", 10, 60),
          trace.Event("leaf", 20, 30), trace.Event("leaf", 70, 80)]
    got = {(e.name, e.start): (s, leaf)
           for e, s, leaf in trace.self_times(ev)}
    assert got[("outer", 0)] == (40, False)
    assert got[("mid", 10)] == (40, False)
    assert got[("leaf", 20)] == (10, True)
    assert got[("leaf", 70)] == (10, True)


@pytest.mark.parametrize("name,kind", [
    ("all-reduce.3", "all-reduce"), ("all-reduce-start.1", "all-reduce"),
    ("all-gather-done.12", "all-gather"), ("reduce-scatter", "reduce-scatter"),
    ("collective-permute-start.2", "collective-permute"),
    ("all-to-all.7", "all-to-all"), ("fusion.3", None),
    ("all-reduce-scatter-fusion", None), ("convolution.1", None),
])
def test_collective_kind(name, kind):
    assert trace.collective_kind(name) == kind


def test_async_pairs_match_by_suffix():
    ev = [trace.Event("all-gather-start.1", 0, 1),
          trace.Event("all-gather-start.2", 1, 2),
          trace.Event("all-gather-done.2", 5, 6),
          trace.Event("all-gather-done.1", 8, 9)]
    assert sorted(trace.collective_intervals(ev)) == [
        (0, 1), (0, 9), (1, 2), (1, 6)]


@pytest.mark.parametrize("text,name,group", [
    ("%fusion.3037 = (f32[128,12,196]{2,1,0:T(8,128)S(1)}, f32[128,12,196,196]"
     "{2,3,1,0:T(8,128)}) fusion(f32[128,12,196,196]{2,3,1,0:T(8,128)} "
     "%get-tuple-element.18143), kind=kLoop, calls=%fused_computation.2818",
     "fusion.3037", "fusion[kLoop] f32[128,12,196]"),
    ("%multiply_add_fusion.1334 = (f32[1024,4096]{1,0:T(8,128)}, f32[1024,4096]"
     "{1,0}) fusion(f32[1024,4096]{1,0} %x), kind=kOutput, calls=%f.1",
     "multiply_add_fusion.1334", "multiply_add_fusion[kOutput] f32[1024,4096]"),
    ("%copy-start.169 = (f32[1,196,768]{2,1,0}, u32[]{:S(2)}) copy-start("
     "f32[1,196,768]{2,1,0} %x)", "copy-start.169",
     "copy-start f32[1,196,768]"),
    ("%all-reduce-start.5 = f32[768]{0} all-reduce-start(f32[768]{0} %x)",
     "all-reduce-start.5", "all-reduce-start f32[768]"),
    ("fusion.123", "fusion.123", "fusion"),
    ("copy", "copy", "copy"),
])
def test_parse_hlo(text, name, group):
    assert trace.parse_hlo(text) == (name, group)


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert trace.find_xplane(str(tmp_path)) == str(d / "host.xplane.pb")


def test_describe_lists_every_line(planes):
    got = trace.describe(planes)
    assert got["/device:TPU:1 | XLA Ops"]["events"] == 3
    assert got["/device:TPU:0 | Async XLA Ops"]["events"] == 1
    assert got["/host:CPU | main"]["top"][0][1] == pytest.approx(1100 * NS)
