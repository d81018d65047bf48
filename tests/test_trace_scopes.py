"""What the program says of itself to a profiler (CPU, tiny ViT): the
scopes of the compiled train epoch's ops, the names of the epoch programs,
and the trainer's spans of one pass. ``benchmark/scopes.py`` reads all
three from a device trace.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.train import steps
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.trainer import Trainer
from pytorch_distributed_mnist_tpu.utils.profiling import profile_trace

FORWARD = "jit(train_epoch)/while/body/closed_call/jvp(VisionTransformer)/"
BACKWARD = ("jit(train_epoch)/while/body/closed_call/"
            "transpose(jvp(VisionTransformer))/")


def _state():
    model = get_model("vit", patch_size=7, embed_dim=32, depth=2,
                      num_heads=2, compute_dtype=jnp.float32)
    return create_train_state(model, jax.random.key(0))


def _batches():
    rng = np.random.default_rng(0)
    return {"image": jnp.asarray(rng.normal(size=(2, 4, 28, 28, 1)),
                                 jnp.float32),
            "label": jnp.asarray(rng.integers(0, 10, (2, 4)), jnp.int32)}


@pytest.fixture(scope="module")
def op_names():
    """Every ``op_name`` of the compiled train epoch."""
    compiled = steps.make_train_epoch().lower(_state(), _batches()).compile()
    return set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


@pytest.mark.parametrize("prefix", [
    "jit(train_epoch)/while/body/closed_call/optimizer/",
    "jit(train_epoch)/while/body/closed_call/jvp(loss)/",
    "jit(train_epoch)/while/body/closed_call/transpose(jvp(loss))/",
    "jit(train_epoch)/while/body/closed_call/loss/",
    FORWARD + "block0/attn/attn_core/", BACKWARD + "block0/attn/attn_core/",
    FORWARD + "block1/mlp/mlp1/", BACKWARD + "block1/mlp/mlp2/",
    FORWARD + "block0/attn/qkv/", BACKWARD + "block0/attn/qkv/",
])
def test_compiled_ops_carry_the_scope(op_names, prefix):
    assert any(name.startswith(prefix) for name in op_names), sorted(
        n for n in op_names if n.startswith("jit("))[:40]


@pytest.mark.parametrize("train,indexed,name", [
    (True, False, "train_epoch"), (True, True, "train_epoch_indexed"),
    (False, False, "eval_epoch"), (False, True, "eval_epoch_indexed"),
])
def test_epoch_programs_are_named(train, indexed, name):
    state, batches = _state(), _batches()
    step_fn = steps.make_accum_train_step_fn(1) if train else None
    fn = steps._make_epoch(None, "data", None, step_fn, train, indexed)
    if indexed:
        data = {"image": batches["image"][0], "label": batches["label"][0]}
        ticks = {"idx": jnp.zeros((2, 4), jnp.int32),
                 "mask": jnp.ones((2, 4), bool)}
        lowered = fn.lower(state, data, ticks)
    else:
        lowered = fn.lower(state, batches)
    assert re.search(r"module @jit_(\w+)", lowered.as_text()).group(1) == name


def test_scopes_change_names_only(monkeypatch):
    """With ``jax.named_scope`` a no-op (flax's module scopes included) the
    same parameters come out of the same epoch, bit for bit."""
    def one_epoch():
        state, metrics = steps.make_train_epoch()(_state(), _batches())
        return jax.device_get((state.params, metrics))

    with_scopes = one_epoch()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = one_epoch()
    assert jax.tree_util.tree_structure(with_scopes) \
        == jax.tree_util.tree_structure(without)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(with_scopes[0])]
    assert "['params']['block0']['mlp1']['kernel']" in paths
    assert not any("attn_core" in p or "['mlp']" in p for p in paths)
    for a, b in zip(jax.tree.leaves(with_scopes), jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b)


def test_a_pass_holds_the_trainers_five_spans(tmp_path):
    rng = np.random.default_rng(0)
    images = rng.normal(size=(64, 28, 28, 1)).astype(np.float32)
    labels = (np.arange(64) % 10).astype(np.int32)
    state = create_train_state(
        get_model("linear", compute_dtype=jnp.float32), jax.random.key(0))
    loaders = [MNISTDataLoader(images, labels, batch_size=16, train=t, seed=7)
               for t in (True, False)]
    trainer = Trainer(state, *loaders, mode="scan")
    with profile_trace(str(tmp_path)):
        trainer.train()
        trainer.close()  # joins the thread that stages the next pass
    found = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1
    thread_of = {}
    for plane in ProfileData.from_file(str(found[0])).planes:
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("trainer:"):
                    thread_of.setdefault(ev.name, set()).add(
                        (plane.name, index))
    assert set(thread_of) == {
        "trainer:input_wait", "trainer:dispatch", "trainer:read_metrics",
        "trainer:stack_epoch", "trainer:h2d"}
    assert all(len(threads) == 1 for threads in thread_of.values())
    assert thread_of["trainer:input_wait"] == thread_of["trainer:dispatch"] \
        == thread_of["trainer:read_metrics"]
    assert thread_of["trainer:stack_epoch"] == thread_of["trainer:h2d"] \
        != thread_of["trainer:dispatch"]
