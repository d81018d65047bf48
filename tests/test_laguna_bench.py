"""The benchmark's ``laguna-xs2-ep8`` configuration and its cell
``train_laguna_ep8_8k``: the files as they are, and the runner
``train_lm`` end to end on the CPU at a tiny preset, added to a temporary
copy of the benchmark the way a later PR adds a cell."""

import contextlib
import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from bench_helpers import add_cell, make_bench_root, write_spec  # noqa: E402

from benchmark import flash_cost, peaks, scopes_lm, trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import laguna as ref  # noqa: E402
from pytorch_distributed_mnist_tpu.models import decoder  # noqa: E402

CELL = "train_laguna_ep8_8k"
TINY_CONFIG = {
    "name": "tiny-laguna",
    "source": "none: a CPU test preset, not a published architecture",
    "model": "laguna",
    "kwargs": {
        "seq_len": 64, "vocab_size": 256, "hidden_size": 64, "head_dim": 16,
        "num_kv_heads": 2,
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention",
                        "full_attention"],
        "heads_per_layer": [4, 6, 6, 6, 4],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
        "window": 8, "rope": decoder.TINY_ROPE, "dense_mlp_size": 256,
        "expert_size": 32, "shared_expert_size": 32, "num_experts": 16,
        "top_k": 4, "experts_held": [4, 8], "routed_scale": 2.5,
        "rms_eps": 1e-6, "remat": True},
    "dtype": "f32",
    "reference": "laguna",
    "reduced": [],
}
TINY_JOB = {"runner": "train_lm", "seq_len": 64, "batch_per_chip": 2,
            "steps_per_pass": 2, "lr": 1e-3,
            "documents": {"median_len": 16, "sigma": 1.0, "min_len": 4,
                          "max_len": 64, "zipf_exponent": 1.0}}


def spec_and_config():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-xs2-ep8.json")) as f:
        return spec, json.load(f)


def run_cell(root, cell, *, traced=False, seed=2**31 + 5):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        line = harness.run_cell(cell, seed, 0.2, traced, root=root,
                                require_platform="cpu", cache_dir="")
    return line, [json.loads(x) for x in out.getvalue().splitlines()]


def note(notes, kind):
    return next(n for n in notes if n.get("kind") == kind)


@pytest.fixture()
def tiny_root(tmp_path):
    root, spec = make_bench_root(tmp_path)
    add_cell(root, spec, name="tiny_laguna", config=TINY_CONFIG,
             traffic={"name": "tiny_lm", **TINY_JOB}, chips=1)
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_laguna")
    write_spec(root, spec)
    return root


def test_the_cell_and_its_files_are_in_the_benchmark():
    spec, cfg = spec_and_config()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "laguna-xs2-ep8", "chips": 1,
                    "traffic": "train_lm_packed_8k_b2"}
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    traffic = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", f"{cell['traffic']}.json"))
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["steps_per_pass"], traffic["lr"]) == (8192, 2, 2, 1e-4)
    assert traffic["documents"] == {
        "median_len": 1024, "sigma": 1.0, "min_len": 16, "max_len": 8192,
        "zipf_exponent": 1.0}
    ours = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in ours} == {
        "moe_router_ms_per_step", "moe_dispatch_ms_per_step",
        "moe_experts_ms_per_step", "attn_full_ms_per_step",
        "attn_window_ms_per_step", "moe_load_max_over_mean",
        "moe_local_pair_share", "moe_dropped_pairs",
        "train_tokens_per_s_per_chip", "flash_fwd_roofline",
        "flash_bwd_roofline"}
    for m in ours:
        assert m["moves"] == "train_images_per_s_per_chip"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layers", f"{m['name']}.py"))


def test_no_width_differs_from_the_source():
    _, cfg = spec_and_config()
    kw = cfg["kwargs"]
    assert (kw["hidden_size"], kw["head_dim"], kw["num_kv_heads"],
            kw["dense_mlp_size"], kw["expert_size"],
            kw["shared_expert_size"], kw["top_k"], kw["window"],
            kw["routed_scale"], kw["rms_eps"]) == (
        cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
        cfg["sliding_window"], cfg["moe_routed_scaling_factor"],
        cfg["rms_norm_eps"]) == (2048, 128, 8, 8192, 512, 512, 8, 512,
                                 2.5, 1e-6)
    depth = cfg["num_hidden_layers"]
    assert depth == 5 and len(cfg["layer_types"]) == 40
    assert kw["layer_types"] == cfg["layer_types"][:depth] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert kw["heads_per_layer"] \
        == cfg["num_attention_heads_per_layer"][:depth] == [48, 64, 64, 64, 48]
    assert kw["mlp_layer_types"] == cfg["mlp_layer_types"][:depth]
    assert kw["rope"]["full_attention"] \
        == cfg["rope_parameters"]["full_attention"]
    assert kw["rope"]["sliding_attention"] \
        == cfg["rope_parameters"]["sliding_attention"]
    # The cut: depth, experts held, vocabulary, each beside its source.
    assert cfg["published"]["num_hidden_layers"] == 40
    assert (kw["num_experts"], cfg["published"]["num_experts"],
            cfg["num_experts"], kw["experts_held"]) == (256, 256, 32, [0, 32])
    assert (kw["vocab_size"], cfg["vocab_size"],
            cfg["published"]["vocab_size"]) == (12544, 12544, 100352)
    assert "8 chips" in cfg["deployment"]
    assert {"gating", "router", "residual", "qk_norm", "shared_expert_gate",
            "window", "documents"} <= set(cfg["assumed"])


def test_the_cut_holds_the_bytes_and_work_counted_from_its_shapes():
    """ISSUE 27's counts (766.7M parameters, 0.95 GFLOP a token forward)
    less its element-wise attention gates, 75.5M parameters and 0.15
    GFLOP a token: the gate is one a head (the file's ``assumed``)."""
    _, cfg = spec_and_config()
    kw = ref.model_kwargs(cfg["kwargs"])
    assert ref.param_count(kw) == pytest.approx(691.6e6, rel=1e-3)
    per_token = ref.forward_flops_per_sequence(kw, 8192) / 8192
    assert per_token == pytest.approx(0.802e9, rel=0.01)
    assert ref.train_flops_per_image(cfg["kwargs"]) \
        == pytest.approx(19.7e12, rel=0.01)
    calls = flash_cost.layer_calls(kw, batch=2, seq_len=8192)
    assert calls["full"]["h"] == 48 and calls["window"]["h"] == 64
    full = flash_cost.forward(**calls["full"])
    window = flash_cost.forward(**calls["window"])
    # A window layer's core is a small share of a full layer's.
    assert window["flops"] / full["flops"] == pytest.approx(
        64 * flash_cost.causal_pairs(8192, 512)
        / (48 * flash_cost.causal_pairs(8192)))
    assert 0.15 < window["flops"] / full["flops"] < 0.17
    assert flash_cost.backward(**calls["full"])["flops"] \
        == 2.5 * full["flops"]


def test_the_gate_a_head_is_what_the_published_size_counts():
    """The source says ``gating: true`` and no shape. The model uncut, with
    one gate a head, has the 33.4B parameters and 3.0B active ones its
    card states (Laguna XS.2 33B-A3B); a gate an element (ISSUE 27's
    reading) would add 0.63B to both: 34.1B-A3.6B."""
    _, cfg = spec_and_config()
    kw = dict(ref.model_kwargs(cfg["kwargs"]),
              layer_types=cfg["layer_types"],
              heads_per_layer=cfg["num_attention_heads_per_layer"],
              mlp_layer_types=cfg["mlp_layer_types"], experts_held=None,
              vocab_size=cfg["published"]["vocab_size"])
    total = ref.param_count(kw)
    gates = sum(kw["hidden_size"] * h for h in kw["heads_per_layer"])
    idle = 39 * (256 - 8) * 3 * 2048 * 512  # the experts a token skips
    assert round(total / 1e9, 1) == 33.4 and round((total - idle) / 1e9) == 3
    by_element = total + gates * (kw["head_dim"] - 1)
    assert round(by_element / 1e9, 1) == 34.1
    assert round((by_element - idle) / 1e9, 1) == 3.6


def test_lm_scope_classes_and_kernel_names():
    jvp = "jit(train_epoch)/while/body/closed_call/jvp(Decoder)/block2"
    assert scopes_lm.classify(f"{jvp}/moe/router/dot_general") \
        == "moe_router"
    assert scopes_lm.classify(f"{jvp}/moe/dispatch/sort") == "moe_dispatch"
    assert scopes_lm.classify(f"{jvp}/moe/combine/gather") == "moe_dispatch"
    assert scopes_lm.classify(f"{jvp}/moe/experts/ragged_dot") \
        == "moe_experts"
    assert scopes_lm.classify(f"{jvp}/moe/shared/up/dot_general") \
        == "moe_experts"
    assert scopes_lm.classify(
        f"{jvp}/attn/attn_core/window/attn_core/pallas_call") \
        == "attn_window"
    assert scopes_lm.classify(f"{jvp}/attn/attn_core/full/x") == "attn_full"
    assert scopes_lm.classify(f"{jvp}/attn/q/dot_general") is None
    # XLA's grouped-matmul kernel keeps no scope but its own name; a scope
    # entered outside a custom_vjp is printed inside its wrapper.
    assert scopes_lm.classify("ragged-dot-none") == "moe_experts"
    assert scopes_lm.classify(
        "jit(f)/transpose(jvp(block1/attn/attn_core/window))/attn_core/x") \
        == "attn_window"
    assert scopes_lm.kind_of(f"{jvp}/attn/attn_core/full/x") == "full"
    assert scopes_lm.kernel_of("%flash_bwd_dkv.3 = custom-call", "") \
        == "flash_bwd_dkv"
    # the one backward kernel (ops/pallas/flash.py) reads as the first
    assert scopes_lm.kernel_of("%flash_bwd_dq_dkv.3 = custom-call", "") \
        == "flash_bwd_dq"
    assert scopes_lm.kernel_of("custom-call.7", f"{jvp}/flash_fwd") \
        == "flash_fwd"
    assert scopes_lm.kernel_of("fusion.1", jvp) is None


def test_backward_roofline_reads_one_kernel_a_call():
    """``flash_bwd_roofline`` takes the calls of ``flash_bwd_dq`` and the
    seconds of both backward names; a program whose backward is one kernel
    leaves ``flash_bwd_dkv`` at 0 calls and 0 s and still reads a number:
    the need of the calls over the one kernel's time."""
    from types import SimpleNamespace

    from benchmark import flash_cost

    def cell(s, calls):
        return {"s": s, "calls": calls}

    found = {"kernels": {
        "flash_fwd": {"full": cell(0.065, 4), "window": cell(0.042, 12)},
        "flash_bwd_dq": {"full": cell(0.060, 2), "window": cell(0.048, 6)},
        "flash_bwd_dkv": {"full": cell(0.0, 0), "window": cell(0.0, 0)}}}
    config = spec_and_config()[1]
    run = SimpleNamespace(
        config=config,
        counters={"scopes_lm": found, "batch": 2, "chips": 1,
                  "tokens_per_image": 8192, "device_kind": "TPU v5 lite"})
    share = flash_cost.roofline_share(
        run, ("flash_bwd_dq", "flash_bwd_dkv"), flash_cost.backward)
    calls = flash_cost.layer_calls(config["kwargs"], batch=2, seq_len=8192)
    least = sum(n * flash_cost.backward(**calls[kind])["flops"] / 197e12
                for kind, n in (("full", 2), ("window", 6)))
    assert share == pytest.approx(100.0 * least / 0.108)
    assert 0 < share < 100


def test_tiny_cell_runs_correct_and_counts_its_routing(tiny_root):
    line, notes = run_cell(tiny_root, "tiny_laguna")
    check = note(notes, "reference_check")
    assert check["ok"], check
    # logits, loss, eleven leaves, the share of changed expert choices
    assert len(check["errors"]) == 14 and check["errors"]["choice_flips"] == 0
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s_per_chip",
                                    "setup_s"}
    routing = note(notes, "routing")
    assert routing["dropped"] == 0 and routing["landed"] > 0
    # 2 steps a pass, four sparse layers; 2 x 64 tokens x top-4 a step
    passes = note(notes, "passes")["n"]
    assert routing["summands"] == passes * 2 * 4
    assert routing["routed"] == passes * 2 * 4 * 2 * 64 * 4
    assert 0.2 < routing["local_pair_share"] < 0.8  # 8 of 16 held
    assert routing["load_max_over_mean"] >= 1.0
    assert note(notes, "setup")["compiles_in_window"] == 0


def tiny_check(seed, system):
    """The runner's own comparison at the tiny size, held to the limits of
    a configuration that states bf16; ``system`` is 'model' in bfloat16 or
    'float8 reference'."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.data.tokens import (
        synthetic_token_corpus,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy

    lm = harness.load_module(
        os.path.join(REPO, "benchmark", "runners", "train_lm.py"),
        "runners/train_lm")
    config = {**TINY_CONFIG, "dtype": "bf16"}
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model("laguna", compute_dtype=jnp.bfloat16, **kwargs)
    tokens, labels = synthetic_token_corpus(
        1, 64, kwargs["vocab_size"], seed=seed, median_len=16, min_len=4)
    params = model.init(jax.random.key(seed), jnp.zeros((1, 64)))
    if system == "float8 reference":
        return lm.check_lower_precision(ref, config, params, tokens, labels)
    return lm.check_against_reference(
        ref, config, lm.model_forward(model),
        lambda logits, y: cross_entropy(logits, y, None),
        params, tokens, labels)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_in_float8_is_not_correct_where_bf16_is_stated(seed):
    """The control of ``TOLERANCES['bf16']``: the reference with its
    weights rounded to float8, the nearest precision below the stated one,
    through the runner's comparison, is refused, and by the logits, every
    named gradient and the share of changed expert choices at once. The
    model in bfloat16 is inside the same limits (but for the loss, whose
    error averages over 63 tokens here and 8,191 in the cell)."""
    low = tiny_check(seed, "float8 reference")
    assert not low["ok"]
    over = {k for k in low["errors"] if low["errors"][k] > low["limits"][k]}
    assert over >= set(low["errors"]) - {"loss"}, low
    sound = tiny_check(seed, "model")
    assert len(sound["errors"]) == 14  # logits, loss, 11 leaves, flips
    assert not [k for k in sound["errors"]
                if k != "loss" and sound["errors"][k] > sound["limits"][k]
                ], sound


@pytest.fixture()
def fake_trace(monkeypatch):
    """As tests/benchmark's ``fixture_trace``: a CPU trace has no device
    plane and the table of peaks no CPU, so the traced path reads the
    hand-made two-chip trace and is lent a peak."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "hbm_bytes_per_s": 1e11})
    planes = trace.load(os.path.join(
        REPO, "tests", "benchmark", "fixtures", "two_chips.xplane.pb"))
    monkeypatch.setattr(trace, "load", lambda path: planes)


def test_tiny_cell_traced_reports_the_counters_and_no_device_number(
        tiny_root, fake_trace):
    line, _ = run_cell(tiny_root, "tiny_laguna", traced=True)
    metrics = line["metrics"]
    assert line["correct"] is True
    assert {"moe_load_max_over_mean", "moe_local_pair_share",
            "moe_dropped_pairs", "train_tokens_per_s_per_chip", "step_ms",
            "mfu"} <= set(metrics)
    assert metrics["moe_dropped_pairs"]["value"] == 0
    assert metrics["train_tokens_per_s_per_chip"]["unit"] == "tokens/s/chip"
    # A CPU trace holds no device plane: nothing read from one is reported.
    assert not {"moe_router_ms_per_step", "attn_window_ms_per_step",
                "flash_fwd_roofline", "flash_bwd_roofline"} & set(metrics)


def test_a_vit_cell_reports_none_of_the_new_metrics(tiny_root, fake_trace):
    """The eleven readers list the new cell alone: a ViT cell's traced line
    is what it was. (``correct`` is not asserted: ``runners/train.py``
    counts the interpreted Pallas calls of the whole test process.)"""
    line, _ = run_cell(tiny_root, "tiny_1chip", traced=True)
    assert "step_ms" in line["metrics"]
    assert not [m for m in line["metrics"]
                if m.startswith(("moe_", "flash_", "attn_full", "attn_window",
                                 "train_tokens"))]
