"""The benchmark's ``granite-4.0-h-micro-vp8`` configuration and its cell
``train_granite4h_vp8_8k``: the files as they are, and the runner
``train_lm_plain`` end to end on the CPU at a tiny preset, added to a
temporary copy of the benchmark the way a later PR adds a cell."""

import contextlib
import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))

from bench_helpers import add_cell, make_bench_root, write_spec  # noqa: E402

from benchmark import peaks, scopes, scopes_ssd, ssd_cost, trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import granite4h as ref  # noqa: E402

CELL = "train_granite4h_vp8_8k"
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro-vp8.json")
OURS = ["ssd_scan_ms_per_step", "ssd_proj_ms_per_step", "ssd_fwd_roofline",
        "ssd_bwd_roofline", "ssd_state_bytes_kept"]
# The catalog row of granite-4.0-h-micro (the model-configs guide's
# architectures.jsonl, ``config``): every key of it but ``layer_types``,
# which is below.
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
TINY_KWARGS = {
    "seq_len": 48, "vocab_size": 256, "hidden_size": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_d_conv": 4, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "rms_eps": 1e-5, "remat": True}
TINY_CONFIG = {
    "name": "tiny-granite",
    "source": "none: a CPU test preset, not a published architecture",
    "model": "granite_hybrid", "kwargs": TINY_KWARGS, "dtype": "f32",
    "reference": "granite4h", "reduced": []}
TINY_JOB = {"runner": "train_lm_plain", "seq_len": 48, "batch_per_chip": 1,
            "steps_per_pass": 2, "lr": 1e-3,
            "documents": {"median_len": 16, "sigma": 1.0, "min_len": 4,
                          "max_len": 48, "zipf_exponent": 1.0}}


def spec_and_config():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(CONFIG_FILE) as f:
        return spec, json.load(f)


def run_cell(root, cell, *, traced=False, seed=2**31 + 11):
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
    add_cell(root, spec, name="tiny_granite", config=TINY_CONFIG,
             traffic={"name": "tiny_lm_plain", **TINY_JOB}, chips=1)
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_granite")
    write_spec(root, spec)
    return root


def test_the_cell_and_its_files_are_in_the_benchmark():
    spec, cfg = spec_and_config()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "granite-4.0-h-micro-vp8", "chips": 1,
                    "traffic": "train_lm_packed_8k_b1"}
    # the seventh cell, where PR 37 appended it; later PRs append after it
    assert spec["workloads"][6] == cell
    # one four-chip cell still
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert spec["configs"][5] == entry
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/ibm-granite/granite-4.0-h-micro" \
           "/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    traffic = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", f"{cell['traffic']}.json"))
    assert (traffic["runner"], traffic["seq_len"], traffic["batch_per_chip"],
            traffic["steps_per_pass"], traffic["lr"]) \
        == ("train_lm_plain", 8192, 1, 2, 1e-4)
    assert traffic["documents"] == {
        "median_len": 1024, "sigma": 1.0, "min_len": 16, "max_len": 8192,
        "zipf_exponent": 1.0}
    ours = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in ours] == OURS
    first = spec["per_layer"].index(ours[0])  # appended together, in order
    assert spec["per_layer"][first:first + 5] == ours
    for m in ours:
        assert m["moves"] == "train_images_per_s_per_chip"
        assert m["unit"] == {"ssd_fwd_roofline": "%", "ssd_bwd_roofline": "%",
                             "ssd_state_bytes_kept": "bytes"}.get(
            m["name"], "ms")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layers", f"{m['name']}.py"))
    # The other models' own readers stay theirs alone.
    for name, cell_of in (("flash_fwd_roofline", "train_laguna_ep8_8k"),
                          ("ssm_scan_ms_per_step", "train_phi4flash_vp8_16k"),
                          ("ssm_state_bytes_kept", "train_phi4flash_vp8_16k"),
                          ("mla_proj_ms_per_step", "train_instella_ep8_8k")):
        metric = next(m for m in spec["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [cell_of]


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
            kw["num_kv_heads"], kw["rms_eps"]) == (
        cfg["hidden_size"], cfg["shared_intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["rms_norm_eps"])
    assert kw["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    assert (kw["mamba_n_heads"], kw["mamba_d_head"], kw["mamba_d_state"],
            kw["mamba_d_conv"]) == (
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        cfg["mamba_d_conv"]) == (64, 64, 128, 4)
    assert kw["mamba_n_heads"] * kw["mamba_d_head"] \
        == cfg["mamba_expand"] * cfg["hidden_size"]
    assert [kw[m] for m in ("embedding_multiplier", "residual_multiplier",
                            "attention_multiplier", "logits_scaling")] \
        == [12, 0.22, 1 / 64, 8] \
        == [cfg[m] for m in ("embedding_multiplier", "residual_multiplier",
                             "attention_multiplier", "logits_scaling")]
    # the chunk is the shape's, and the shape's is the source's
    from pytorch_distributed_mnist_tpu.ops.pallas.ssd import chunk_length

    assert chunk_length(kw["seq_len"]) == cfg["mamba_chunk_size"] \
        == ref.CHUNK == ssd_cost.CHUNK
    # The cut: the 40 kinds as published, the ten built, the vocabulary.
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and cfg["num_hidden_layers"] == 10
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    assert all(k == "mamba" for i, k in enumerate(kinds) if i % 10 != 5)
    assert cfg["layers_built"] == list(range(10))
    assert kw["layer_types"] == kinds[:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (kw["vocab_size"], cfg["vocab_size"]) == (12544, 12544)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "8" in cfg["deployment"] and "vocabulary" in cfg["deployment"]
    assert {"initialisation", "dt", "mixer", "attention", "mlp",
            "documents", "chunk", "precision"} <= set(cfg["assumed"])
    assert kw["remat"] is True and kw["seq_len"] == 8192
    assert cfg["model"] == "granite_hybrid" and cfg["dtype"] == "bf16"


def test_the_two_parameter_counts_and_the_work_counted_from_shapes():
    """772,160,448 parameters here and, uncut, 3,191,396,096: the card's
    3B, which ties the equations to the source."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model

    _, cfg = spec_and_config()
    kw = ref.model_kwargs(cfg["kwargs"])
    mamba_layer = 17_432_576 + 21_760 + 3 * 64 + 4_096 + 8_388_608 \
        + 50_331_648 + 4_096
    attention_layer = 10_485_760 + 50_331_648 + 4_096
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    assert ref.param_count(kw) == 772_160_448 == (
        9 * mamba_layer + attention_layer + 12_544 * 2048 + 2048)
    built = jax.eval_shape(
        get_model("granite_hybrid", **kw).init, jax.random.key(0),
        jnp.zeros((1, 128)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(built)) \
        == 772_160_448
    uncut = dict(kw, layer_types=cfg["layer_types"],
                 vocab_size=cfg["published"]["vocab_size"])
    assert ref.param_count(uncut) == 3_191_396_096 == (
        36 * mamba_layer + 4 * attention_layer + 205_520_896 + 2048)
    forward = ref.forward_flops_per_sequence(kw, 8192)
    # 2 x parameters x tokens, the one causal core and the nine scans
    scans = 9 * ref.ssd_flops_per_sequence(
        tokens=8192, heads=64, d_head=64, d_state=128)
    core = 4 * ref.causal_pairs(8192) * 32 * 64
    in_no_matrix = 2048 * 21 + 9 * (21_760 + 3 * 64 + 4_096)  # norms, conv
    assert forward == pytest.approx(
        2 * (772_160_448 - in_no_matrix) * 8192 + core + scans, rel=1e-9)
    assert ref.train_flops_per_image(cfg["kwargs"]) \
        == pytest.approx(39.5e12, rel=5e-3)
    assert 3 * core == pytest.approx(0.82e12, rel=0.01)
    assert 3 * scans == pytest.approx(0.70e12, rel=0.01)
    assert 10 * 6 * 8192 * 2048 * 8192 / forward \
        == pytest.approx(0.626, abs=0.005)  # the MLPs' share
    # what a scan needs: the two bounds nearly meet, bytes first
    shape, layers = ssd_cost.scan_calls(kw, batch=1, seq_len=8192)
    assert layers == 9 and shape == dict(b=1, t=8192, h=64, p=64, n=128)
    cost = ssd_cost.forward(**shape)
    assert cost["flops"] == scans / 9 == 32 * (
        2 * 32896 * 128 + 64 * (2 * 32896 * 64 + 4 * 256 * 64 * 128))
    assert cost["bytes"] == 8192 * (2 * 4096 * 2 + 2 * 128 * 2 + 4 * 64)
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert (cost["bytes"] / v5e["hbm_bytes_per_s"]) \
        / (cost["flops"] / v5e["bf16_flops"]) == pytest.approx(1.29, abs=0.02)
    back = ssd_cost.backward(**shape)
    assert back["flops"] == 2 * cost["flops"] and back["bytes"] > cost["bytes"]
    # the per-position states it never holds, and what it keeps instead
    assert 8192 * 64 * 64 * 128 * 4 == pytest.approx(17.2e9, rel=0.01)
    assert 32 * 64 * 64 * 128 * 4 == 67_108_864


def test_ssd_scope_classes():
    jvp = "jit(train_epoch)/while/body/closed_call/jvp(GraniteHybrid)/block2"
    back = jvp.replace("jvp(GraniteHybrid)",
                       "transpose(jvp(GraniteHybrid))")
    assert scopes_ssd.classify(f"{jvp}/ssd/scan/ssd_scan/ssd_fwd") \
        == "ssd_scan"
    assert scopes_ssd.classify(f"{back}/ssd/scan/ssd_scan/cumsum") \
        == "ssd_scan"
    for part in ("in_proj/dot_general", "conv/mul", "dt/softplus",
                 "norm/rsqrt", "mul", "out_proj/dot_general"):
        assert scopes_ssd.classify(f"{jvp}/ssd/{part}") == "ssd_proj"
    assert scopes_ssd.classify(f"{jvp}/attn/attn_core/full/x") is None
    assert scopes_ssd.classify(f"{jvp}/mlp/down/dot_general") is None
    assert scopes_ssd.classify(f"{jvp}/ssm/scan/selective_scan/x") is None
    # a scope entered outside a custom_vjp is printed inside its wrapper
    assert scopes_ssd.classify(
        "jit(f)/transpose(jvp(block0/ssd/scan))/ssd_scan/ssd_bwd") \
        == "ssd_scan"
    # In scopes.py's fixed table: ssd has no class; the attention layer,
    # the MLPs, the norms and the ends have the classes they always had.
    assert scopes.classify("fusion.1", f"{jvp}/ssd/scan/x") == "unscoped"
    assert scopes.classify("fusion.1", f"{jvp}/ssd/norm/x") == "unscoped"
    assert scopes.classify("fusion.1", f"{jvp}/attn/q/x") == "attn_proj"
    assert scopes.classify("fusion.1", f"{jvp}/attn/attn_core/full/x") \
        == "attn_core"
    assert scopes.classify("fusion.1", f"{jvp}/mlp/gate_up/x") == "mlp"
    assert scopes.classify("fusion.1", f"{jvp}/ln1/x") == "norm"
    assert scopes.classify("fusion.1", "jit(f)/jvp(GraniteHybrid)/head/dot") \
        == "ends"


def test_tiny_cell_runs_correct_and_counts_its_scans(tiny_root):
    line, notes = run_cell(tiny_root, "tiny_granite")
    check = note(notes, "reference_check")
    assert check["ok"], check
    assert len(check["errors"]) == 15  # logits, loss, thirteen leaves
    # ``correct`` is false here and here alone: on the CPU the scan's
    # kernels are interpreted, which the runner refuses as it must.
    setup = note(notes, "setup")
    assert setup["pallas_lowerings"]["interpret"] > 0 \
        and setup["pallas_lowerings"]["mosaic"] == 0
    assert line["correct"] is False and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s_per_chip",
                                    "setup_s"}
    scans = note(notes, "state_scans")
    assert scans["chunked_sites"] > 0 and scans["sites"] == 0
    # one sequence of 48 positions: 1 chunk of 64, 4 heads of (32, 16)
    assert scans["chunked_chunks_per_site"] == 1
    assert scans["chunked_state_bytes_kept_per_site"] == 4 * 32 * 16 * 4
    assert setup["compiles_in_window"] == 0
    assert note(notes, "flash_schedules")["kept_results"] >= 0


def tiny_check(seed, system):
    """The runner's own comparison at the tiny size, held to the limits of
    a configuration that states bf16; ``system`` is 'model' in bfloat16 or
    'float8 reference'. (The model in bfloat16 is held to these limits on
    the chip, tests_tpu/test_granite4h_on_tpu.py.)"""
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
    model = get_model("granite_hybrid", compute_dtype=jnp.bfloat16, **kwargs)
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
    assert [k for k in low["errors"]
            if low["errors"][k] > low["limits"][k]], low


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
    line, _ = run_cell(tiny_root, "tiny_granite", traced=True)
    metrics = line["metrics"]
    assert line["failed"] == 0
    assert {"ssd_state_bytes_kept", "step_ms", "mfu"} <= set(metrics)
    assert metrics["ssd_state_bytes_kept"] == {"value": 8192.0,
                                               "unit": "bytes"}
    # A CPU trace holds no device plane: nothing read from one is reported.
    assert not {"ssd_scan_ms_per_step", "ssd_proj_ms_per_step",
                "ssd_fwd_roofline", "ssd_bwd_roofline"} & set(metrics)
    # Phi-4's counter reads the selective scans alone: none ran here.
    assert "ssm_state_bytes_kept" not in metrics


def test_the_readers_on_the_fixture_trace_find_nothing_and_say_so():
    """The hand-made two-chip trace has device ops and none under an
    ``ssd`` scope, as the parent's program has: the reduction gives zero
    seconds in both classes and a reader ``None``, not a raise."""
    with open(os.path.join(REPO, "tests", "benchmark", "fixtures",
                           "two_chips.xplane.pb"), "rb") as f:
        found = scopes_ssd.reduce(f.read())
    assert found["devices"] == 2
    assert set(found["classes"]) == {"ssd_scan", "ssd_proj"}
    assert all(part == 0.0 for parts in found["classes"].values()
               for part in parts.values())
    assert found["rows"] == []

    class Run:
        counters = {"scopes_ssd": found, "steps_per_pass": 2,
                    "traced_passes": 2}

    assert scopes_ssd.class_ms_per_step(Run, "ssd_scan") is None
    assert ssd_cost.roofline_share(Run, "forward") is None
    assert ssd_cost.roofline_share(Run, "backward") is None


def test_other_cells_report_none_of_the_new_metrics(tiny_root, fake_trace):
    """The five readers list the new cell alone: a ViT cell's traced line
    is what it was. (``correct`` is not asserted: ``runners/train.py``
    counts the interpreted Pallas calls of the whole test process.)"""
    line, _ = run_cell(tiny_root, "tiny_1chip", traced=True)
    assert "step_ms" in line["metrics"]
    assert not [m for m in line["metrics"] if m.startswith("ssd_")]
