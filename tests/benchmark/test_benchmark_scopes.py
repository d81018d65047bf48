"""benchmark/scopes.py on a hand-made two-chip trace of the train epoch.

``fixtures/scoped.xspace.txt`` carries an op's scope where the v5e's trace
does: in the stat ``tf_op`` of the event's metadata entry (``%fusion.8``'s as
a reference to a stat metadata entry, the others' as a string). In
nanoseconds, with each op's scope below ``jit(train_epoch)/while/body/
closed_call/`` and F = ``jvp(VisionTransformer)``, B = ``transpose(F)``:

    chip 0, XLA Modules:  jit_train_epoch(123) [100,1000],
        jit_convert_element_type(7) [1005,1010]
    chip 0, XLA Ops:  while.1 [100,900] (scope ``jit(train_epoch)/while``)
        holding fusion.1 [100,200] F/block0/attn/qkv, fusion.2 [200,300]
        F/block0/attn/attn_core, fusion.3 [300,380] F/block1/mlp/mlp1,
        fusion.4 [380,400] F/block1/ln1, fusion.5 [400,420] jvp(loss),
        all-reduce-start.1 [420,430] (no scope), fusion.6 [430,560]
        B/block1/mlp/mlp2, all-reduce-done.1 [560,600] (no scope),
        fusion.7 [600,700] B/block0/attn/attn_core, fusion.8 [700,800]
        optimizer, copy.9 [800,850] (no scope); then fusion.10 [950,1000]
        F/reduce_sum (the pool)
    chip 1, XLA Modules:  jit_train_epoch(123) [100,1050]
    chip 1, XLA Ops:  fusion.1 [100,300], fusion.12 [300,500]
        F/block1/attn/attn_core, all-reduce.2 [500,600] (scope
        B/block0/mlp/mlp1: a collective whatever its scope), fusion.6
        [600,800], fusion.8 [800,1050]
    host, one thread:  bench:window [0,1100], bench:train_pass [20,1080],
        trainer:input_wait [20,30], trainer:dispatch [30,120],
        trainer:read_metrics [120,1080]
    host, another thread:  trainer:stack_epoch [130,400], trainer:h2d
        [400,500]

So, chip 0 / chip 1 / mean: collective 50 / 100 / 75 (backward: chip 1's
100); optimizer 100 / 250 / 175; attn_core 200 / 200 / 200 (backward: chip
0's 100); attn_proj 100 / 200 / 150; mlp 210 / 200 / 205 (forward: chip 0's
80); norm 20 / 0 / 10; ends 70 / 0 / 35; unscoped 100 (copy.9 and the 50 of
while.1 that no child covers) / 0 / 50. Busy 850 / 950 / 900, the sum of the
classes. Chip 0 idles in [0,100] (70 of it under trainer:dispatch),
[900,950] and [1000,1100] (trainer:read_metrics), chip 1 in [0,100] and
[1050,1100]: means 100 and 100.
"""

import contextlib
import io
import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark import peaks, scopes, trace
from benchmark import run as harness

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NS = 1e-9
BODY = "jit(train_epoch)/while/body/closed_call/"
NEW = [m + "_ms_per_step" for m in (
    "attn_core", "attn_proj", "mlp", "norm", "optimizer", "unscoped")] \
    + ["device_step_ms"]


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(FIXTURES, "scoped.xspace.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def xspace(text):
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def reduced(xspace):
    return scopes.reduce(xspace)


@pytest.mark.parametrize("name,total,forward,backward", [
    ("collective", 75, 25, 50), ("optimizer", 175, 175, 0),
    ("attn_core", 200, 150, 50), ("attn_proj", 150, 150, 0),
    ("mlp", 205, 40, 165), ("norm", 10, 10, 0), ("ends", 35, 35, 0),
    ("unscoped", 50, 50, 0),
])
def test_class_seconds_forward_and_backward(reduced, name, total, forward,
                                            backward):
    assert reduced["classes"][name] == {
        "s": pytest.approx(total * NS),
        "forward_s": pytest.approx(forward * NS),
        "backward_s": pytest.approx(backward * NS, abs=1e-18)}


def test_classes_partition_the_busy_self_time(reduced, xspace):
    assert tuple(reduced["classes"]) == scopes.CLASS_NAMES
    assert not reduced["stale"] and reduced["devices"] == 2
    # The same events through trace.py: their self times, summed.
    planes = trace._planes(ProfileData.from_serialized_xspace(xspace))
    self_ns = sum(s for events in trace.device_ops(planes).values()
                  for _ev, s, _leaf in trace.self_times(events))
    assert self_ns == 850 + 950
    by_class_ns = sum(c["s"] for c in reduced["classes"].values()) / NS * 2
    assert abs(by_class_ns - self_ns) < 1e-3
    assert reduced["busy_self_s"] == pytest.approx(
        trace.reduce(planes)["busy_s"], rel=1e-12)


def test_module_time_gaps_and_rows(reduced, xspace):
    assert reduced["window_s"] == pytest.approx(1100 * NS)
    assert reduced["module_s"] == {
        "/device:TPU:0": pytest.approx(900 * NS),
        "/device:TPU:1": pytest.approx(950 * NS)}
    assert reduced["idle_gaps"] == {
        "trainer:dispatch": pytest.approx(100 * NS),
        "trainer:read_metrics": pytest.approx(100 * NS)}
    assert sum(reduced["idle_gaps"].values()) + reduced["busy_self_s"] \
        == pytest.approx(reduced["window_s"])
    rows = reduced["rows"]
    assert rows[0] == ["optimizer", BODY + "optimizer/add",
                       "fusion[kLoop] f32[1024,4096]",
                       pytest.approx(175 * NS)]
    # block0's and block1's forward cores are one row.
    assert ["attn_core", BODY + "jvp(VisionTransformer)/block*/attn/"
            "attn_core/reduce_sum", "fusion[kLoop] f32[32,16,196]",
            pytest.approx(150 * NS)] in rows
    assert sum(r[3] for r in rows) == pytest.approx(reduced["busy_self_s"])
    assert len(scopes.reduce(xspace, rows=3)["rows"]) == 3


@pytest.mark.parametrize("instruction,scope,want", [
    ("all-gather-done.3", BODY + "optimizer/add", "collective"),
    ("fusion.1", BODY + "optimizer/jit(_where)/select_n", "optimizer"),
    ("fusion.1", BODY + "transpose(jvp(VisionTransformer))/block11/attn/"
     "attn_core/bhqk,bkhd->bqhd/dot_general", "attn_core"),
    ("copy.4", BODY + "jvp(VisionTransformer)/block3/attn/slice",
     "attn_proj"),
    ("fusion.1", BODY + "jvp(VisionTransformer)/block3/mlp/mul", "mlp"),
    ("fusion.1", BODY + "jvp(VisionTransformer)/ln_f/reduce_sum", "norm"),
    ("fusion.1", BODY + "transpose(jvp(VisionTransformer))/head/dot_general",
     "ends"),
    ("fusion.1", BODY + "transpose(jvp(loss))/mul", "ends"),
    ("fusion.1", BODY + "loss/metrics_update/reduce_sum", "ends"),
    ("fusion.1", BODY + "transpose(jvp(VisionTransformer))/add_any", "ends"),
    ("copy-done.7", "jit(train_epoch)/while", "unscoped"),
    ("fusion.1", "", "unscoped"),
])
def test_classify(instruction, scope, want):
    assert scopes.classify(instruction, scope) == want


def _plane(name: bytes, event_metadata: bytes) -> bytes:
    body = b"\x12" + bytes([len(name)]) + name \
        + b"\x22" + bytes([len(event_metadata)]) + event_metadata
    return b"\x0a" + bytes([len(body)]) + body


def test_wire_reader_reads_the_serialised_text(text, xspace):
    got = scopes.op_scopes(xspace)
    assert set(got) == {"/device:TPU:0", "/device:TPU:1"}
    # Every metadata entry with the stat, by string or by reference.
    assert len(got["/device:TPU:0"]) == text.split(
        'name: "/device:TPU:1"')[0].count("stats { metadata_id: 1 ") == 10
    by_instruction = {trace.parse_hlo(k)[0]: v
                      for k, v in got["/device:TPU:0"].items()}
    assert by_instruction["fusion.8"] == BODY + "optimizer/add"
    assert by_instruction["fusion.2"] == \
        BODY + "jvp(VisionTransformer)/block0/attn/attn_core/reduce_sum"
    assert by_instruction["while.1"] == "jit(train_epoch)/while"
    assert "copy.9" not in by_instruction
    # A plane that is no device's is passed over by its length: one whose
    # metadata is not a message at all changes nothing, where the same
    # bytes under a device's name cannot be read.
    junk = b"\xff\xff\xff"
    assert scopes.op_scopes(xspace + _plane(b"/host:junk", junk)) == got
    with pytest.raises((IndexError, ValueError)):
        scopes.op_scopes(xspace + _plane(b"/device:TPU:9", junk))


def _run_over(tmp_path, bench_root, xspace):
    """A traced run's ``Run`` whose raw trace is ``xspace``."""
    root, spec = bench_root
    run = harness.Run(root=root, cell=spec["workloads"][0], config={},
                      traffic={}, seed=0, seconds=1, trace=True,
                      require_platform="cpu", started_at=0.0, cache_dir="")
    run.reduced_trace = {"busy_s": 0.0}
    run.counters.update(steps_per_pass=2, traced_passes=2)
    logdir = os.path.join(run.scratch_dir("trace"), "plugins", "profile", "x")
    os.makedirs(logdir)
    with open(os.path.join(logdir, "host.xplane.pb"), "wb") as f:
        f.write(xspace)
    return run


def _read_all(run):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        values = {m: run.module("layers", m).read(run) for m in NEW}
    return values, [json.loads(x) for x in out.getvalue().splitlines()]


def test_readers_give_milliseconds_a_step(tmp_path, bench_root, xspace):
    run = _run_over(tmp_path, bench_root, xspace)
    values, notes = _read_all(run)
    steps = 2 * 2
    assert values == {
        "attn_core_ms_per_step": pytest.approx(200e-6 / steps),
        "attn_proj_ms_per_step": pytest.approx(150e-6 / steps),
        "mlp_ms_per_step": pytest.approx(205e-6 / steps),
        "norm_ms_per_step": pytest.approx(10e-6 / steps),
        "optimizer_ms_per_step": pytest.approx(175e-6 / steps),
        "unscoped_ms_per_step": pytest.approx(50e-6 / steps),
        "device_step_ms": pytest.approx(925e-6 / steps)}
    # Reduced once, noted on one earlier line, written whole beside it.
    assert [n["kind"] for n in notes] == ["scopes"]
    assert notes[0]["stale"] is False and "rows" not in notes[0]
    with open(run.out_path("scopes.json")) as f:
        assert len(json.load(f)["rows"]) == 14


def test_stale_names_report_nothing(tmp_path, bench_root, text):
    """An executable from before the scopes: flax's names and none of the
    program's. Nothing is reported; a zero would be a lie."""
    old = text.replace("/attn_core", "").replace("/optimizer", "")
    run = _run_over(tmp_path, bench_root,
                    ProfileData.text_proto_to_serialized_xspace(old))
    values, notes = _read_all(run)
    assert values == dict.fromkeys(NEW)
    assert [n["stale"] for n in notes] == [True]
    assert scopes.of(run) is None


def test_a_cpu_traced_run_reports_none_of_them(bench_root, monkeypatch):
    """A CPU trace has no device plane: the seven find nothing to read and
    the run still prints the contract's line (as in
    test_benchmark_harness.py, trace.py is lent the two-chip fixture)."""
    root, _spec = bench_root
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12})
    planes = trace.load(os.path.join(FIXTURES, "two_chips.xplane.pb"))
    monkeypatch.setattr(trace, "load", lambda path: planes)
    with contextlib.redirect_stdout(io.StringIO()):
        line = harness.run_cell("tiny_1chip", 3, 0.2, True, root=root,
                                require_platform="cpu", cache_dir="")
    assert tuple(line) == harness.RESULT_KEYS + ("breakdown",)
    assert line["correct"] is True
    assert not set(NEW) & set(line["metrics"])
    assert "step_ms" in line["metrics"]
    assert not os.path.exists(os.path.join(
        root, "chiprun_out", "benchmark", "tiny_1chip.scopes.json"))
