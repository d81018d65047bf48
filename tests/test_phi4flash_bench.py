"""The benchmark's ``phi4-mini-flash-vp8`` configuration and its cell
``train_phi4flash_vp8_16k``: the files as they are, and the runner
``train_lm_plain`` end to end on the CPU at a tiny preset, added to a
temporary copy of the benchmark the way a later PR adds a cell."""

import contextlib
import hashlib
import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from bench_helpers import add_cell, make_bench_root, write_spec  # noqa: E402

from benchmark import peaks, scopes, scopes_ssm, ssm_cost, trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import phi4flash as ref  # noqa: E402

CELL = "train_phi4flash_vp8_16k"
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "phi4-mini-flash-vp8.json")
# The catalog row of Phi-4-mini-flash-reasoning (the model-configs guide's
# architectures.jsonl, ``config``): every number of it.
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
TINY_KWARGS = {
    "seq_len": 48, "vocab_size": 256, "hidden_size": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_size": 128,
    "layer_types": ["mamba", "sliding_attention", "mamba", "full_attention",
                    "gmu", "cross_attention"],
    "layer_ids": [0, 1, 16, 17, 18, 19], "window": 8, "d_inner": 128,
    "d_state": 4, "d_conv": 4, "dt_rank": 4, "layer_norm_eps": 1e-5,
    "remat": True}
TINY_CONFIG = {
    "name": "tiny-sambay",
    "source": "none: a CPU test preset, not a published architecture",
    "model": "sambay", "kwargs": TINY_KWARGS, "dtype": "f32",
    "reference": "phi4flash", "reduced": []}
TINY_JOB = {"runner": "train_lm_plain", "seq_len": 48, "batch_per_chip": 1,
            "steps_per_pass": 2, "lr": 1e-3,
            "documents": {"median_len": 16, "sigma": 1.0, "min_len": 4,
                          "max_len": 48, "zipf_exponent": 1.0}}


def spec_and_config():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(CONFIG_FILE) as f:
        return spec, json.load(f)


def run_cell(root, cell, *, traced=False, seed=2**31 + 7):
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
    add_cell(root, spec, name="tiny_sambay", config=TINY_CONFIG,
             traffic={"name": "tiny_lm_plain", **TINY_JOB}, chips=1)
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_sambay")
    write_spec(root, spec)
    return root


def test_the_cell_and_its_files_are_in_the_benchmark():
    spec, cfg = spec_and_config()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "phi4-mini-flash-vp8", "chips": 1,
                    "traffic": "train_lm_packed_16k_b1"}
    # the fifth cell, where PR 31 appended it; later PRs append after it
    assert spec["workloads"][4] == cell
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning" \
           "/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    traffic = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", f"{cell['traffic']}.json"))
    assert (traffic["runner"], traffic["seq_len"], traffic["batch_per_chip"],
            traffic["steps_per_pass"], traffic["lr"]) \
        == ("train_lm_plain", 16384, 1, 2, 1e-4)
    assert traffic["documents"] == {
        "median_len": 1024, "sigma": 1.0, "min_len": 16, "max_len": 16384,
        "zipf_exponent": 1.0}
    ours = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in ours] == [
        "ssm_scan_ms_per_step", "ssm_proj_ms_per_step", "gmu_ms_per_step",
        "attn_cross_ms_per_step", "attn_diff_ms_per_step",
        "ssm_scan_fwd_roofline", "ssm_scan_bwd_roofline",
        "ssm_state_bytes_kept"]
    first = spec["per_layer"].index(ours[0])
    assert spec["per_layer"][first:first + 8] == ours
    for m in ours:
        assert m["moves"] == "train_images_per_s_per_chip"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layers", f"{m['name']}.py"))
    # Laguna's own readers stay Laguna's alone.
    for name in ("flash_fwd_roofline", "attn_full_ms_per_step",
                 "train_tokens_per_s_per_chip"):
        metric = next(m for m in spec["per_layer"] if m["name"] == name)
        assert metric["workloads"] == ["train_laguna_ep8_8k"]


def test_no_width_differs_from_the_catalogs_row():
    _, cfg = spec_and_config()
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_how"]
        else:
            assert cfg[key] == value, key
    kw = cfg["kwargs"]
    assert (kw["hidden_size"], kw["mlp_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["window"], kw["layer_norm_eps"]) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["sliding_window"], cfg["layer_norm_eps"])
    assert kw["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    # Mamba-1's defaults, which the source leaves unset (``assumed``)
    assert (kw["d_inner"], kw["d_state"], kw["d_conv"], kw["dt_rank"]) == (
        2 * 2560, 16, 4, -(-2560 // 16))
    # The cut: the 32 kinds the rule gives, the six built, the vocabulary.
    kinds = cfg["layer_types"]
    assert len(kinds) == 32 and cfg["num_hidden_layers"] == 6
    assert all((k in ("mamba", "gmu")) == (i % 2 == 0)
               for i, k in enumerate(kinds))  # mb_per_layer 2
    assert [i for i, k in enumerate(kinds) if k == "mamba"] \
        == list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [17]
    assert cfg["layers_built"] == kw["layer_ids"] == [0, 1, 16, 17, 18, 19]
    assert kw["layer_types"] == [kinds[i] for i in cfg["layers_built"]] == [
        "mamba", "sliding_attention", "mamba", "full_attention", "gmu",
        "cross_attention"]
    assert (kw["vocab_size"], cfg["vocab_size"]) == (25008, 25008)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "8" in cfg["deployment"] and "vocabulary" in cfg["deployment"]
    assert {"state_space", "layer_kinds", "differential_attention",
            "biases", "positions", "window", "documents", "precision",
            "initialisation"} <= set(cfg["assumed"])
    assert kw["remat"] is True and kw["seq_len"] == 16384


def test_the_two_parameter_counts_and_the_work_counted_from_shapes():
    """697,094,272 parameters here and, uncut, 3,852,562,944: the card's
    3.8B, which ties what the file assumes to the source."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model

    _, cfg = spec_and_config()
    kw = ref.model_kwargs(cfg["kwargs"])
    assert ref.param_count(kw) == 697_094_272 == (
        2 * 119_895_040 + 2 * 98_322_304 + 104_867_840 + 91_766_144
        + 64_020_480 + 5_120)
    built = jax.eval_shape(
        get_model("sambay", **kw).init, jax.random.key(0),
        jnp.zeros((1, 128)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(built)) \
        == 697_094_272
    uncut = dict(kw, layer_types=cfg["layer_types"], layer_ids=None,
                 vocab_size=cfg["published"]["vocab_size"])
    assert ref.param_count(uncut) == 3_852_562_944
    assert 3.8e9 <= ref.param_count(uncut) < 3.9e9
    forward = ref.forward_flops_per_sequence(kw, 16384)
    assert forward == pytest.approx(27.08e12, rel=1e-3)
    assert ref.train_flops_per_image(cfg["kwargs"]) \
        == pytest.approx(81.25e12, rel=1e-3)
    assert 6 * 6 * 16384 * 2560 * 10240 / forward \
        == pytest.approx(0.57, abs=0.005)  # the MLPs' share
    # one score map a query head, keys 64 and values 128 wide, two layers
    cores = 2 * 2 * ref.causal_pairs(16384) * 40 * (64 + 128)
    assert cores / forward == pytest.approx(0.152, abs=0.003)
    # what a scan needs: bytes bound it, by a factor of 17
    shape, layers = ssm_cost.scan_calls(kw, batch=1, seq_len=16384)
    assert layers == 2 and shape == dict(b=1, t=16384, c=5120, n=16)
    cost = ssm_cost.forward(**shape)
    assert cost["flops"] == 7 * 16384 * 5120 * 16
    assert cost["bytes"] == 16384 * (2 * 5120 * 2 + 4 * 5120 + 2 * 16 * 2)
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert (cost["bytes"] / v5e["hbm_bytes_per_s"]) \
        / (cost["flops"] / v5e["bf16_flops"]) == pytest.approx(17, abs=1)
    back = ssm_cost.backward(**shape)
    assert back["flops"] == 2 * cost["flops"] and back["bytes"] > cost["bytes"]


def test_ssm_scope_classes():
    jvp = "jit(train_epoch)/while/body/closed_call/jvp(SambaY)/block2"
    back = jvp.replace("jvp(SambaY)", "transpose(jvp(SambaY))")
    assert scopes_ssm.classify(f"{jvp}/ssm/scan/selective_scan/while") \
        == "ssm_scan"
    for part in ("in_proj/dot_general", "conv/mul", "x_proj/dot_general",
                 "dt/dt_proj/dot_general", "gate/mul",
                 "out_proj/dot_general"):
        assert scopes_ssm.classify(f"{jvp}/ssm/{part}") == "ssm_proj"
    assert scopes_ssm.classify(f"{back}/gmu/in_proj/dot_general") == "gmu"
    assert scopes_ssm.classify(
        f"{jvp}/attn/attn_core/cross/attn_core/pallas_call") == "attn_cross"
    assert scopes_ssm.classify(f"{jvp}/attn/diff/rsqrt") == "attn_diff"
    assert scopes_ssm.classify(f"{jvp}/attn/attn_core/full/x") is None
    assert scopes_ssm.classify(f"{jvp}/mlp/down/dot_general") is None
    # a scope entered outside a custom_vjp is printed inside its wrapper
    assert scopes_ssm.classify(
        "jit(f)/transpose(jvp(block0/ssm/scan))/selective_scan/while") \
        == "ssm_scan"
    # In scopes.py's fixed table: ssm and gmu have no class, the combine
    # is the attention module's, the cross core the core's, the norms norm.
    assert scopes.classify("fusion.1", f"{jvp}/ssm/scan/x") == "unscoped"
    assert scopes.classify("fusion.1", f"{jvp}/gmu/in_proj/x") == "unscoped"
    assert scopes.classify("fusion.1", f"{jvp}/attn/diff/x") == "attn_proj"
    assert scopes.classify("fusion.1", f"{jvp}/attn/attn_core/cross/x") \
        == "attn_core"
    assert scopes.classify("fusion.1", f"{jvp}/mlp/gate_up/x") == "mlp"
    assert scopes.classify("fusion.1", f"{jvp}/ln1/x") == "norm"
    assert scopes.classify("fusion.1", "jit(f)/jvp(SambaY)/head/dot") \
        == "ends"


def test_tiny_cell_runs_correct_and_counts_its_scans(tiny_root):
    line, notes = run_cell(tiny_root, "tiny_sambay")
    check = note(notes, "reference_check")
    assert check["ok"], check
    assert len(check["errors"]) == 13  # logits, loss, eleven leaves
    # ``correct`` is false here and here alone: on the CPU the scan's
    # kernels are interpreted, which the runner refuses as it must.
    setup = note(notes, "setup")
    assert setup["pallas_lowerings"]["interpret"] > 0 \
        and setup["pallas_lowerings"]["mosaic"] == 0
    assert line["correct"] is False and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s_per_chip",
                                    "setup_s"}
    scans = note(notes, "state_scans")
    assert scans["sites"] > 0 and scans["memory_readers"] > 0 \
        and scans["kv_readers"] > 0
    # one sequence of 48 positions: 1 chunk of 64, a (4, 128) float32 state
    assert scans["chunks_per_site"] == 1
    assert scans["state_bytes_kept_per_site"] == 128 * 4 * 4
    assert setup["compiles_in_window"] == 0
    assert "folded_sites" in note(notes, "flash_schedules")


def tiny_check(seed, system):
    """The runner's own comparison at the tiny size, held to the limits of
    a configuration that states bf16; ``system`` is 'model' in bfloat16 or
    'float8 reference'. (The model in bfloat16 is held to these limits on
    the chip, tests_tpu/test_phi4flash_on_tpu.py.)"""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.data.tokens import (
        synthetic_token_corpus,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy

    def runner(name):
        return harness.load_module(
            os.path.join(REPO, "benchmark", "runners", f"{name}.py"),
            f"runners/{name}")

    lm, plain = runner("train_lm"), runner("train_lm_plain")
    config = {**TINY_CONFIG, "dtype": "bf16"}
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model("sambay", compute_dtype=jnp.bfloat16, **kwargs)
    tokens, labels = synthetic_token_corpus(
        1, 48, kwargs["vocab_size"], seed=seed, median_len=16, min_len=4)
    params = jax.jit(model.init)(jax.random.key(seed), jnp.zeros((1, 48)))
    if system == "float8 reference":
        return plain.check_lower_precision(
            lm, ref, config, params, tokens, labels)
    return plain.check_against_reference(
        lm, ref, config, lambda p, x: model.apply(p, x, train=True),
        lambda logits, y: cross_entropy(logits, y, None),
        params, tokens, labels)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_in_float8_is_not_correct_where_bf16_is_stated(seed):
    """The control of ``TOLERANCES['bf16']``: the reference with its
    weights rounded to float8, the nearest precision below the stated one,
    through the runner's comparison, is refused by at least one limit."""
    low = tiny_check(seed, "float8 reference")
    assert not low["ok"], low
    assert low["errors"]["logits"] > low["limits"]["logits"], low
    assert low["limits"]["grad:params/block1/attn/lq1"] \
        == ref.TOLERANCES["bf16"]["grad:lq1"] \
        > low["limits"]["grad:params/block1/attn/subln"] \
        == ref.TOLERANCES["bf16"]["grad"]


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


def test_tiny_cell_traced_reports_the_counter_and_no_device_number(
        tiny_root, fake_trace):
    line, _ = run_cell(tiny_root, "tiny_sambay", traced=True)
    metrics = line["metrics"]
    assert line["failed"] == 0
    assert {"ssm_state_bytes_kept", "step_ms", "mfu"} \
        <= set(metrics)
    assert metrics["ssm_state_bytes_kept"] == {"value": 2048.0,
                                               "unit": "bytes"}
    # A CPU trace holds no device plane: nothing read from one is reported.
    assert not {"ssm_scan_ms_per_step", "ssm_proj_ms_per_step",
                "gmu_ms_per_step", "attn_cross_ms_per_step",
                "attn_diff_ms_per_step", "ssm_scan_fwd_roofline",
                "ssm_scan_bwd_roofline"} & set(metrics)


def test_other_cells_report_none_of_the_new_metrics(tiny_root, fake_trace):
    """The eight readers list the new cell alone: a ViT cell's traced line
    is what it was. (``correct`` is not asserted: ``runners/train.py``
    counts the interpreted Pallas calls of the whole test process.)"""
    line, _ = run_cell(tiny_root, "tiny_1chip", traced=True)
    assert "step_ms" in line["metrics"]
    assert not [m for m in line["metrics"]
                if m.startswith(("ssm_", "gmu_", "attn_cross", "attn_diff"))]


def test_lagunas_pass_lowers_to_what_it_did():
    """``models/decoder.py attend`` learnt a scale and a third kind of core
    for this model; the calls ``laguna`` makes lower to the text they
    lowered to before it did. The digest is taken with this function: PR
    31's parent's until PR 32 made the flash backward one kernel, PR 32's
    tree's until PR 36 took the recomputed forward kernel out of a
    recomputed block (``decoder.recomputed`` keeps its two results), PR
    36's until PR 39 put the gate a head on the packed view, since then PR
    39's tree's."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import decoder, get_model

    kwargs = dict(
        vocab_size=256, hidden_size=64, head_dim=16, num_kv_heads=2,
        layer_types=["full_attention", "sliding_attention"],
        heads_per_layer=[4, 6], mlp_layer_types=["dense", "sparse"],
        window=8, rope=decoder.TINY_ROPE, dense_mlp_size=128,
        expert_size=32, shared_expert_size=32, num_experts=8, top_k=2,
        remat=True, attention="flash")
    model = get_model("laguna", compute_dtype=jnp.bfloat16, **kwargs)
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)

    def loss(p, x):
        return jnp.sum(model.apply(p, x, train=True))

    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    assert len(text) > 100_000  # the kernels, interpreted, are in it
    assert hashlib.sha256(text.encode()).hexdigest() == LAGUNA_LOWERED


LAGUNA_LOWERED = (
    "851a4a5d6f28189b5cfa1a907882338576b9545780139110de0c6f203d15c515")
