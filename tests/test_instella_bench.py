"""The benchmark's ``instella-moe-16b-ep8`` configuration and its cell
``train_instella_ep8_8k``: the files as they are, and the runner
``train_lm_mtp`` end to end on the CPU at a tiny preset, added to a
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

from benchmark import peaks, scopes, scopes_mla, trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import instella as ref  # noqa: E402
from pytorch_distributed_mnist_tpu.models import instella  # noqa: E402

CELL = "train_instella_ep8_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"mla_proj_ms_per_step", "mtp_ms_per_step",
               "router_bias_range"}
TINY_CONFIG = {
    "name": "tiny-instella",
    "source": "none: a CPU test preset, not a published architecture",
    "model": "instella",
    "kwargs": {
        "seq_len": 64, "vocab_size": 256, "hidden_size": 64, "num_heads": 4,
        "nope_dim": 12, "rope_dim": 4, "v_dim": 16, "kv_rank": 32,
        "mlp_layer_types": ["dense", "sparse", "sparse"],
        "rope": instella.TINY_ROPE, "dense_mlp_size": 256,
        "expert_size": 32, "shared_expert_size": 64, "num_experts": 16,
        "top_k": 4, "experts_held": [4, 8], "routed_scale": 2.5,
        "rms_eps": 1e-6, "remat": True},
    "dtype": "f32",
    "reference": "instella",
    "reduced": [],
}
TINY_JOB = {"runner": "train_lm_mtp", "seq_len": 64, "batch_per_chip": 2,
            "steps_per_pass": 2, "lr": 1e-3, "mtp_weight": 0.3,
            "aux_weight": 1e-4, "bias_rate": 1e-3,
            "documents": {"median_len": 16, "sigma": 1.0, "min_len": 4,
                          "max_len": 64, "zipf_exponent": 1.0}}


def spec_and_config():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "instella-moe-16b-ep8.json")) as f:
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
    add_cell(root, spec, name="tiny_instella", config=TINY_CONFIG,
             traffic={"name": "tiny_lm_mtp", **TINY_JOB}, chips=1)
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_instella")
    write_spec(root, spec)
    return root


def test_the_cell_and_its_files_are_in_the_benchmark():
    spec, cfg = spec_and_config()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "instella-moe-16b-ep8", "chips": 1,
                    "traffic": "train_lm_mtp_packed_8k_b2"}
    # the sixth cell, where PR 33 appended it; later PRs append after it
    assert spec["workloads"][5] == cell
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    job = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", f"{cell['traffic']}.json"))
    laguna = harness.load_json(os.path.join(
        REPO, "benchmark", "traffic", "train_lm_packed_8k_b2.json"))
    # The job is train_laguna_ep8_8k's, plus the objective's three numbers.
    same = ("seq_len", "batch_per_chip", "steps_per_pass", "trainer_mode",
            "epoch_gather", "grad_accum", "loss", "optimizer",
            "optimizer_sharding", "lr", "documents")
    assert {k: job[k] for k in same} == {k: laguna[k] for k in same}
    assert (job["runner"], job["mtp_weight"], job["aux_weight"],
            job["bias_rate"]) == ("train_lm_mtp", 0.3, 1e-4, 1e-3)
    assert {"mtp_weight", "aux_weight", "bias_rate"} <= set(job["assumed"])
    ours = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in ours} == NEW_METRICS
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index("mla_proj_ms_per_step")  # later PRs append after
    assert names[first:first + 3] == [
        "mla_proj_ms_per_step", "mtp_ms_per_step", "router_bias_range"]
    for m in ours:
        assert m["moves"] == "train_images_per_s_per_chip"
        assert m["layer"] == "Step"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layers", f"{m['name']}.py"))


def test_no_width_differs_from_the_catalogs_row():
    """Every number of the catalog's ``config`` stands in the file under
    the same key, but for the three keys ``reduced`` lists; the model's
    kwargs are those numbers."""
    _, cfg = spec_and_config()
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Instella-MoE-16B-A3B-Base")
        assert cfg["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differing == set(cfg["reduced"])
        assert {k: row["config"][k] for k in cfg["reduced"]} == {
            k: cfg["published"][k] for k in cfg["reduced"]}
    kw = cfg["kwargs"]
    assert (kw["hidden_size"], kw["num_heads"], kw["nope_dim"],
            kw["rope_dim"], kw["nope_dim"] + kw["rope_dim"], kw["v_dim"],
            kw["kv_rank"], kw["dense_mlp_size"], kw["expert_size"],
            kw["shared_expert_size"], kw["top_k"], kw["num_experts"],
            kw["routed_scale"], kw["rms_eps"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["qk_head_dim"],
        cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"],
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["published"]["n_routed_experts"],
        cfg["routed_scaling_factor"], cfg["rms_norm_eps"]) == (
        2048, 16, 96, 32, 128, 128, 512, 10944, 1408, 2816, 6, 64, 2.5,
        1e-6)
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["q_lora_rank"] is None
    scaling, rope = cfg["rope_scaling"], kw["rope"]
    assert (rope["rope_type"], rope["rope_theta"], rope["factor"],
            rope["original_max_position_embeddings"], rope["beta_fast"],
            rope["beta_slow"], rope["mscale"], rope["mscale_all_dim"]) == (
        scaling["type"], cfg["rope_theta"], scaling["factor"],
        scaling["original_max_position_embeddings"], scaling["beta_fast"],
        scaling["beta_slow"], scaling["mscale"], scaling["mscale_all_dim"])
    from pytorch_distributed_mnist_tpu.models.decoder import (
        yarn_softmax_scale,
    )
    assert yarn_softmax_scale(128, rope) == pytest.approx(0.1656, rel=1e-3)
    assert ref.softmax_scale(128, rope) == pytest.approx(
        yarn_softmax_scale(128, rope))
    # The cut: depth, experts held, vocabulary, each beside its source.
    depth = cfg["num_hidden_layers"]
    assert depth == 5 and cfg["published"]["num_hidden_layers"] == 27
    assert kw["mlp_layer_types"] == \
        ["dense"] * cfg["first_k_dense_replace"] + ["sparse"] * (depth - 1)
    assert (cfg["n_routed_experts"], kw["experts_held"]) == (8, [0, 8])
    assert (kw["vocab_size"], cfg["vocab_size"],
            cfg["published"]["vocab_size"]) == (16112, 16112, 128896)
    assert cfg["published"]["vocab_size"] == 8 * kw["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 1 and kw["mtp"] is True
    assert kw["farskip"] is cfg["farskip"] is True
    assert kw["gated"] is cfg["gated_attention"] is True
    assert kw["qk_norm"] is cfg["qk_layernorm"] is True
    assert "8 chips" in cfg["deployment"]
    assert {"gated_attention", "qk_layernorm", "farskip", "mtp_input",
            "objective", "router", "shared_experts", "seq_aux", "rope",
            "documents", "precision"} <= set(cfg["assumed"])


def test_the_counts_of_the_cut_and_of_the_uncut_model():
    """ISSUE 33's counts: 668M parameters here (10.7 GB at 16 bytes), and
    uncut 15.9B with 2.8B active a token beside the module: the card's
    16B-A2.8B. With a gate a head the uncut count is 15.75B: the size
    cannot tell the two readings apart."""
    _, cfg = spec_and_config()
    kw = ref.model_kwargs(cfg["kwargs"])
    assert ref.param_count(kw) == pytest.approx(668.0e6, rel=1e-3)
    assert ref.param_count(kw) * 16 == pytest.approx(10.7e9, rel=5e-3)
    uncut = {**kw, "vocab_size": cfg["published"]["vocab_size"],
             "mlp_layer_types": ["dense"] + ["sparse"] * 26,
             "experts_held": None, "mtp": False}
    assert ref.param_count(uncut) == pytest.approx(15.86e9, rel=1e-3)
    assert ref.param_count(uncut, held=kw["top_k"]) \
        == pytest.approx(2.82e9, rel=2e-3)
    per_head = ref.param_count(uncut) - 27 * 2048 * (16 * 128 - 16)
    assert per_head == pytest.approx(15.75e9, rel=1e-3)
    # The module whole: a merge, three norms and one sparse block.
    module = ref.param_count(kw) - ref.param_count({**kw, "mtp": False})
    assert module == pytest.approx(110.6e6, rel=1e-3)
    # The work: 44.7 TFLOP a step of two sequences forward and backward,
    # 59.7 with the blocks' forward recomputed; the attention cores 15.
    flops = ref.train_flops_per_image(cfg["kwargs"])
    assert 2 * flops == pytest.approx(44.7e12, rel=5e-3)
    from benchmark.flash_cost import causal_pairs
    cores = 6 * 2 * 2.0 * causal_pairs(8192, None) * 16 * 256 * 4
    assert cores == pytest.approx(13.2e12, rel=0.01)


def test_mla_scope_classes():
    jvp = "jit(train_epoch)/while/body/closed_call/jvp(Instella)/block2"
    back = jvp.replace("jvp(Instella)", "transpose(jvp(Instella))")
    for part in ("q/q/dot_general", "kv_a/kv_norm/rsqrt", "kv_b/concatenate",
                 "rope/mul", "gate/gate/dot_general", "proj/proj/dot_general"):
        assert scopes_mla.classify(f"{jvp}/attn/mla/{part}") == ["mla_proj"]
        assert scopes_mla.classify(f"{back}/attn/mla/{part}") == ["mla_proj"]
    assert scopes_mla.classify(f"{jvp}/attn/attn_core/full/pallas_call") == []
    assert scopes_mla.classify(f"{jvp}/moe/router/top_k") == []
    module = jvp.rsplit("/", 1)[0] + "/mtp"
    assert scopes_mla.classify(f"{module}/merge/mtp_merge/dot_general") \
        == ["mtp"]
    assert scopes_mla.classify(f"{module}/head/head/dot_general") == ["mtp"]
    # the module's own latent attention is under both
    assert scopes_mla.classify(
        f"{module}/mtp_block/attn/mla/q/q/dot_general") == ["mla_proj", "mtp"]
    assert scopes_mla.classify(
        "jit(train_epoch)/while/body/closed_call/jvp(mtp/loss)/reduce") \
        == ["mtp"]
    assert scopes_mla.classify(
        "jit(train_epoch)/while/body/closed_call/moe/bias/sign") \
        == []  # named in the program, read by no metric
    # In scopes.py's fixed table the latent attention's ops are the
    # attention module's and the core the core's.
    assert scopes.classify("fusion.1", f"{jvp}/attn/mla/q/q/x") == "attn_proj"
    assert scopes.classify("fusion.1", f"{jvp}/attn/attn_core/full/x") \
        == "attn_core"


def test_tiny_cell_runs_correct_and_reports_the_bias_and_the_module(
        tiny_root):
    line, notes = run_cell(tiny_root, "tiny_instella")
    check = note(notes, "reference_check")
    assert check["ok"], check
    # two logit arrays and the choices; of the step two losses, the
    # objective, seventeen leaves' gradient and change, and the bias
    assert len(check["errors"]) == 2 + 1 + 3 + 17 + 17 + 1
    assert check["errors"]["bias"] == 0.0
    assert max(v for k, v in check["errors"].items()
               if k.startswith("update:")) < 1e-3
    assert abs(check["errors"]["choice_flips"]) < 1e-6
    setup = note(notes, "setup")
    assert setup["compiles_in_window"] == 0
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s_per_chip",
                                    "setup_s"}
    routing = note(notes, "routing")
    assert routing["dropped"] == 0 and routing["steps"] >= 6
    assert 0.0 < routing["bias_range"] <= 2 * routing["steps"] * 1e-3
    assert routing["mtp_loss"] > 1.0
    assert routing["local_pair_share"] == pytest.approx(0.5, abs=0.2)
    passes = note(notes, "passes")
    assert len(passes["pass_mtp_losses"]) == passes["n"]
    assert passes["pass_bias_ranges"][-1] >= passes["pass_bias_ranges"][0]


def tiny_check(seed, system="model", dtype="bf16", **planted):
    """The runner's own comparison at the tiny size, held to the limits of
    a configuration that states ``dtype``; ``system`` is 'model' or
    'float8 reference'. ``planted``: what the checked ``Trainer`` is built
    with in place of the job's numbers. (The model in bfloat16 is held to
    bf16's limits on the chip, tests_tpu/test_instella_on_tpu.py.)"""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.tokens import (
        synthetic_token_corpus,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.train.state import (
        train_state_from_params,
    )
    from pytorch_distributed_mnist_tpu.train.trainer import Trainer
    from pytorch_distributed_mnist_tpu.utils.profiling import routing_log

    def runner(name):
        return harness.load_module(
            os.path.join(REPO, "benchmark", "runners", f"{name}.py"),
            f"runners/{name}")

    lm, mtp = runner("train_lm"), runner("train_lm_mtp")
    config = {**TINY_CONFIG, "dtype": dtype}
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model(
        "instella", compute_dtype=getattr(jnp, {"bf16": "bfloat16",
                                                "f32": "float32"}[dtype]),
        **kwargs)
    tokens, labels = synthetic_token_corpus(
        2, 64, kwargs["vocab_size"], seed=seed, median_len=16, min_len=4)
    variables = mtp.with_seeded_bias(
        jax.jit(model.init)(jax.random.key(seed), jnp.zeros((1, 64))), seed)
    if system == "float8 reference":
        return mtp.check_lower_precision(
            lm, ref, config, TINY_JOB, variables, tokens, labels)
    built = {**{k: TINY_JOB[k] for k in (
        "lr", "mtp_weight", "aux_weight", "bias_rate")}, **planted}
    sequences = built.pop("sequences", 2)

    def trainer_of(variables):
        loader = MNISTDataLoader(
            tokens[:sequences], labels[:sequences], batch_size=sequences,
            train=True, seed=seed)
        state = train_state_from_params(model, variables, lr=built.pop("lr"))
        return Trainer(state, loader, loader, **{"mode": "scan", **built})

    routing_log.reset()
    return mtp.check_against_reference(
        lm, ref, config, TINY_JOB, model, variables, tokens, labels,
        trainer_of, routing_log)


def over(check, stated=None):
    """The kinds of number that refuse ``check``, by its own limits or by
    those of a configuration that states ``stated``."""
    tol = ref.TOLERANCES[stated] if stated else None
    return {k.split(":")[0] for k, v in check["errors"].items()
            if v > (tol[k.split(":")[0]] if tol else check["limits"][k])}


@pytest.mark.parametrize("mode", ["scan", "stepwise"])
def test_one_step_of_the_trainer_is_the_references_step(mode):
    """``correct``'s comparison in float32: the scanned pass's program
    (``make_train_epoch``) and the single step's (``make_train_step``),
    through ``Trainer``, give the reference's two losses, objective,
    gradients, first step of Adam and bias, under a seeded non-zero bias."""
    check = tiny_check(0, dtype="f32", mode=mode)
    assert check["ok"], check
    assert check["errors"]["bias"] == 0.0
    assert check["largest"]["bias_entries_apart"] == 0


@pytest.mark.parametrize("planted,refused_by", [
    ({"lr": 0.0}, {"update"}),                      # the state unchanged
    ({"mtp_weight": 0.0},                           # the module's term lost
     {"grad", "grad_routed", "objective"}),
    ({"mtp_weight": 0.1},                           # or given another weight
     {"grad", "grad_routed", "objective"}),
    ({"bias_rate": 0.0}, {"bias"}),                 # the bias never moved
    ({"bias_rate": -1e-3}, {"bias"}),               # or moved the wrong way
    ({"lr": 2e-3}, {"update"}),                     # a step twice as long
    ({"sequences": 1}, {"loss", "grad"}),           # half the batch trained
], ids=["state_unchanged", "mtp_dropped", "mtp_misweighed", "bias_frozen",
        "bias_backwards", "lr_doubled", "half_the_batch"])
def test_a_faulty_step_is_not_correct(planted, refused_by):
    """Faults planted in the step that the check drives come out as not
    ``ok``, each by the number that reads it, also under the wider limits
    of a configuration that states bf16."""
    check = tiny_check(0, dtype="f32", **planted)
    assert not check["ok"]
    assert over(check) >= refused_by, check
    assert over(check, "bf16") >= refused_by, check


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_in_float8_is_not_correct_where_bf16_is_stated(seed):
    """The control of ``TOLERANCES['bf16']``: the reference with its
    weights rounded to float8, the nearest precision below the stated one,
    through the runner's comparison, is refused by at least one limit; the
    step it takes is Adam's of its own gradient, which is not what refuses
    it."""
    low = tiny_check(seed, "float8 reference")
    assert not low["ok"], low
    assert low["errors"]["logits"] > low["limits"]["logits"], low
    assert "update" not in over(low)


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
    line, _ = run_cell(tiny_root, "tiny_instella", traced=True)
    metrics = line["metrics"]
    assert line["failed"] == 0
    assert {"router_bias_range", "step_ms", "mfu"} <= set(metrics)
    assert metrics["router_bias_range"]["unit"] == "ratio"
    assert 0.0 < metrics["router_bias_range"]["value"] < 0.1
    # A CPU trace holds no device plane: nothing read from one is reported.
    assert not {"mla_proj_ms_per_step", "mtp_ms_per_step"} & set(metrics)
    # The Laguna-only lists are not this PR's to widen.
    assert not [m for m in metrics if m.startswith(("moe_", "flash_"))]


def test_other_cells_report_none_of_the_new_metrics(tiny_root, fake_trace):
    """The three readers list the new cell alone: a ViT cell's traced line
    is what it was."""
    line, _ = run_cell(tiny_root, "tiny_1chip", traced=True)
    assert "step_ms" in line["metrics"]
    assert not NEW_METRICS & set(line["metrics"])


def test_a_checkout_without_the_model_fails_at_once(tiny_root, monkeypatch):
    """The parent of the PR that adds the model is given this benchmark's
    files and has to fail in the new cell at once: the runner builds the
    model before it touches a device."""
    from pytorch_distributed_mnist_tpu.models import registry

    monkeypatch.delitem(registry._REGISTRY, "instella")
    with pytest.raises(ValueError, match="unknown model 'instella'"):
        run_cell(tiny_root, "tiny_instella")
