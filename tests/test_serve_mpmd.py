"""MPMD pipeline serving (serve/pipeline.py): the per-stage param split,
the registry plumbing (validate/template/gate), per-stage program
exactness against the single-device forward (padded, exact-bucket, and
chunked), zero steady-state recompiles per bucket x stage, the
coordinated cross-stage hot-reload swap (no mixed-epoch batch), the
pool's chain groups, the stage-occupancy helper, and the analyzer
cleanliness of the new module."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data.mnist import (
    normalize_images,
    synthetic_dataset,
)
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.pipeline_vit import (
    merge_vit_params,
    split_stage_params,
    split_vit_params,
)
from pytorch_distributed_mnist_tpu.serve.batcher import MicroBatcher
from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
from pytorch_distributed_mnist_tpu.serve.pipeline import (
    PipelineEngine,
    make_pipeline_template,
)
from pytorch_distributed_mnist_tpu.serve.pool import EnginePool
from pytorch_distributed_mnist_tpu.serve.programs import (
    check_checkpoint_layout,
    servable_modes,
    serve_modes,
    validate_serve_mode,
)
from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

pytestmark = pytest.mark.serve

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def pp_setup():
    model = get_model("vit", compute_dtype=jnp.float32)
    template = make_pipeline_template(model, jax.random.key(0))
    images, _ = synthetic_dataset(32, seed=5)
    return model, template, images


def _direct_labels(model, split_params, raw_images):
    logits = model.apply(merge_vit_params(split_params), jnp.asarray(
        normalize_images(raw_images)), train=False)
    return np.argmax(np.asarray(logits), axis=-1)


# -- the stage split (parallel/pipeline_vit.py) ------------------------------


def test_split_stage_params_boundaries(pp_setup):
    """Stage s holds blocks [s*k, (s+1)*k) BITWISE (the training stage
    axis's boundaries); embed rides stage 0 only, head the last stage
    only."""
    _, template, _ = pp_setup
    split = template.params
    depth = jax.tree_util.tree_leaves(split["blocks"])[0].shape[0]
    stages = split_stage_params(split, 2)
    assert len(stages) == 2
    assert set(stages[0]) == {"blocks", "embed"}
    assert set(stages[1]) == {"blocks", "head"}
    k = depth // 2
    for s, tree in enumerate(stages):
        got = jax.tree_util.tree_leaves(tree["blocks"])
        want = [np.asarray(leaf)[s * k:(s + 1) * k]
                for leaf in jax.tree_util.tree_leaves(split["blocks"])]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
    # One stage = the whole stack, embed AND head on it.
    (single,) = split_stage_params(split, 1)
    assert set(single) == {"blocks", "embed", "head"}
    with pytest.raises(ValueError, match="not divisible"):
        split_stage_params(split, 3)


def test_pipeline_registered_and_validates(pp_setup):
    """The registry sees the mode (boot gate vocabulary, argparse
    choices) and the generic divisibility walk reduces
    to depth % stages == 0 over the pipelined template tree."""
    _, template, _ = pp_setup
    assert "pipeline" in serve_modes()
    assert servable_modes("vit") == ["replicated", "pipeline", "tensor"]
    validate_serve_mode("pipeline", "vit", 2, template.params)
    with pytest.raises(ValueError, match=r"dim 0 .* does not"):
        # depth 2 does not split 3 ways; the walk names the blocks leaf.
        validate_serve_mode("pipeline", "vit", 3, template.params)
    with pytest.raises(ValueError, match="no sharding rule table"):
        validate_serve_mode("pipeline", "cnn", 2)


def test_layout_gate_flipped_for_pipeline():
    """The PR 8 gate now names --serve-mode pipeline as the VALID choice
    for a pipeline-trained checkpoint instead of rejecting by name, and
    keeps rejecting every other mode for it."""
    check_checkpoint_layout({"pipeline": 2}, "pipeline", "vit")
    with pytest.raises(ValueError, match="--serve-mode pipeline"):
        check_checkpoint_layout({"pipeline": 2}, "replicated", "vit")
    with pytest.raises(ValueError, match="--serve-mode pipeline"):
        check_checkpoint_layout({"pipeline": 2}, "tensor", "vit")
    # A tensor-trained checkpoint still can't serve pipelined.
    with pytest.raises(ValueError, match="--serve-mode tensor"):
        check_checkpoint_layout({"tensor": 2}, "pipeline", "vit")


# -- per-stage program exactness ---------------------------------------------


def test_pipeline_logits_match_single_device(pp_setup):
    """The chained per-stage programs reproduce the single-device
    forward: allclose logits (independent programs reassociate like the
    mesh ones) and identical argmax, at exact-bucket, padded, and
    chunked-oversize batch shapes."""
    model, template, images = pp_setup
    base = InferenceEngine(model.apply, merge_vit_params(template.params),
                           buckets=(1, 8))
    base.warmup()
    eng = PipelineEngine(model, template.params, jax.local_devices()[:2],
                         buckets=(1, 8))
    eng.warmup()
    assert eng.stage_names() == ["pipeline.s0", "pipeline.s1"]
    for n in (8, 5, 1, 20):  # exact bucket, padded, bucket-1, chunked
        got, _ = eng.logits_with_epoch(images[:n])
        ref, _ = base.logits_with_epoch(images[:n])
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.argmax(got, -1),
                                      np.argmax(ref, -1))


def test_zero_steady_state_recompiles_per_bucket_and_stage(pp_setup):
    model, template, images = pp_setup
    eng = PipelineEngine(model, template.params, jax.local_devices()[:2],
                         buckets=(1, 8))
    eng.warmup()
    programs = compile_log.stats()["programs"]
    expected = {f"serve_forward_b{b}@pipeline.s{k}"
                for b in (1, 8) for k in (0, 1)}
    assert expected <= set(programs)
    before = {n: programs[n]["backend_compiles"] for n in expected}
    eng.logits(images[:1])
    eng.logits(images[:8])
    eng.logits(images[:5])  # padded
    eng.logits(images[:20])  # chunked through the top bucket
    after = compile_log.stats()["programs"]
    assert {n: after[n]["backend_compiles"] for n in expected} == before


def test_stage_params_live_on_their_own_chips(pp_setup):
    """The HBM story: stage k's params are committed to chip k ONLY —
    no chip holds the whole model."""
    model, template, _ = pp_setup
    devices = jax.local_devices()[:2]
    eng = PipelineEngine(model, template.params, devices, buckets=(8,))
    for k, stage_tree in enumerate(eng._stage_params):
        for leaf in jax.tree_util.tree_leaves(stage_tree):
            assert leaf.devices() == {devices[k]}


# -- coordinated cross-stage hot reload --------------------------------------


def test_swap_is_stale_rejecting_and_atomic_across_stages(pp_setup):
    model, _, _ = pp_setup
    states = {e: make_pipeline_template(model, jax.random.key(e))
              for e in (1, 2)}
    eng = PipelineEngine(model, states[1].params, jax.local_devices()[:2],
                         buckets=(8,), params_epoch=1)
    eng.warmup()
    assert eng.swap_params(states[2].params, epoch=2) is True
    assert eng.params_epoch == 2
    # Stale swap refused on every stage at once.
    assert eng.swap_params(states[1].params, epoch=1) is False
    assert eng.params_epoch == 2


def test_hot_reload_no_mixed_epoch_batch_under_hammering(pp_setup):
    """The acceptance guarantee: a batch never spans two epochs ACROSS
    STAGES — the per-stage swap installs the whole stage list under one
    lock, dispatch snapshots it once, and every reply's epoch tag is a
    single installed epoch with final logits pinned to the direct
    forward of the final checkpoint."""
    model, _, images = pp_setup
    states = {e: make_pipeline_template(model, jax.random.key(e))
              for e in (10, 11, 12)}
    pool = EnginePool(model.apply, states[10].params,
                      devices=jax.local_devices()[:4], buckets=(1, 8),
                      params_epoch=10, serve_mode="pipeline", mesh_size=2,
                      model_name="vit", model=model)
    pool.warmup()

    def complete(handle):
        labels, epoch = pool.predict_complete(handle)
        tag = np.full_like(labels, -1 if epoch is None else epoch)
        return np.stack([labels, tag], axis=1)

    failures = []
    stop = threading.Event()

    def hammer(wid):
        i = 0
        while not stop.is_set():
            stack = pool.preprocess(images[(wid + i) % 24:
                                           (wid + i) % 24 + 4])
            out = batcher.predict(stack, timeout=30.0)
            epochs = set(out[:, 1].tolist())
            if len(epochs) != 1 or not epochs <= {10, 11, 12}:
                failures.append(out[:, 1].tolist())
            i += 1

    with MicroBatcher(None, max_batch=8, max_wait_s=0.002,
                      dispatch_fn=pool.dispatch, complete_fn=complete,
                      max_inflight=5) as batcher:
        threads = [threading.Thread(target=hammer, args=(w,), daemon=True)
                   for w in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        for epoch in (11, 12):
            assert pool.swap_params(states[epoch].params, epoch=epoch) == 2
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(30.0)
    assert not failures, failures[:5]
    labels, epoch = pool.predict_complete(
        pool.dispatch(pool.preprocess(images[:8])))
    assert epoch == 12
    np.testing.assert_array_equal(
        labels, _direct_labels(model, states[12].params, images[:8]))


# -- the pool's chain groups -------------------------------------------------


def test_pipeline_pool_groups_names_and_spans(pp_setup):
    """4 chips at 2 stages = 2 chains (pipeline.g0/g1), each spanning 2
    disjoint chips, stage programs named per chain x stage; answers
    match a replicated pool of the same checkpoint."""
    model, template, images = pp_setup
    pool = EnginePool(model.apply, template.params,
                      devices=jax.local_devices()[:4], buckets=(1, 8),
                      params_epoch=7, serve_mode="pipeline", mesh_size=2,
                      model_name="vit", model=model)
    assert [r.name for r in pool.replicas] == ["pipeline.g0", "pipeline.g1"]
    spans = [set(map(str, r.devices)) for r in pool.replicas]
    assert len(spans[0]) == 2 and spans[0].isdisjoint(spans[1])
    pool.warmup()
    programs = compile_log.stats()["programs"]
    assert {f"serve_forward_b8@pipeline.g{g}.s{k}"
            for g in (0, 1) for k in (0, 1)} <= set(programs)
    repl = EnginePool(model.apply, merge_vit_params(template.params),
                      devices=jax.local_devices()[:4], buckets=(1, 8),
                      params_epoch=7)
    repl.warmup()
    for n in (8, 3):
        got, ge = pool.predict_complete(pool.dispatch(
            pool.preprocess(images[:n])))
        want, we = repl.predict_complete(repl.dispatch(
            repl.preprocess(images[:n])))
        np.testing.assert_array_equal(got, want)
        assert ge == we == 7
    snap = pool.snapshot()
    for row in snap.values():
        assert row["mode"] == "pipeline" and row["stages"] == 2
    assert pool.topology()["pipeline_stages"] == 2


def test_pipeline_pool_requires_model_object(pp_setup):
    _, template, _ = pp_setup
    model = get_model("vit", compute_dtype=jnp.float32)
    with pytest.raises(ValueError, match="model"):
        EnginePool(model.apply, template.params,
                   devices=jax.local_devices()[:2], serve_mode="pipeline",
                   mesh_size=2, model_name="vit")  # model= missing


# -- analyzer cleanliness ----------------------------------------------------


@pytest.mark.lint
def test_pipeline_module_clean_under_analyzer():
    """serve/pipeline.py pinned clean under the checkers its code could
    plausibly trip: lock discipline (params capture under the engine
    lock vs device work outside), trace purity (the per-stage jitted
    forwards), collective symmetry (no process_index-conditioned
    anything), recompile hazard (bucket lowering)."""
    from tools.analyzer import run_analysis

    result = run_analysis(
        [os.path.join(_REPO, "pytorch_distributed_mnist_tpu", "serve",
                      "pipeline.py")],
        checkers=["collective-symmetry", "trace-purity",
                  "recompile-hazard", "lock-discipline"],
        baseline=None)
    assert result.findings == []
