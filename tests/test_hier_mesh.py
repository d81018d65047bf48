"""Two-tier (DCN x ICI) hierarchical meshes and the tier-aware ZeRO
schedule.

The contract: ``make_hier_mesh`` builds data-major ``('dcn', 'ici',
...)`` meshes from real slice topology (``device.slice_index``) or the
emulated ``TPUMNIST_DCN_SLICES`` map, ``data_replica_coords`` groups
hosts by the COMPOSED data axis, model axes pin inside one slice
(DCN-straddling layouts rejected with flag language), and the two-tier
ZeRO schedule — reduce-scatter over ``ici``, owner-shard all-reduce
over ``dcn``, allgather back over ``ici``, per-tier bucket budgets —
changes WHERE communication happens, never WHAT the training computes:
a 2x2 emulated hierarchy is trajectory-equal to the flat 4-device
propagation AND overlap paths, end to end through the cli, and the
same checkpoints load both ways.
"""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.mesh import (
    DCN_SLICES_ENV,
    HIER_DATA_AXES,
    _slice_blocks,
    data_replica_coords,
    data_sharding,
    device_slice_map,
    infer_dcn_slices,
    is_hier_mesh,
    make_hier_mesh,
    make_mesh,
    resolve_data_axis,
)
from pytorch_distributed_mnist_tpu.parallel.zero import shard_state_zero
from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
    _dcn_bucket_plan,
    _shard_dims,
    make_overlap_train_epoch,
    make_overlap_train_step,
    make_param_gather,
)
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.steps import make_train_step


def _batch(seed, n=64):
    r = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(r.normal(size=(n, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(r.integers(0, 10, size=(n,)), jnp.int32),
    }


def _assert_trees_close(a, b, rtol=2e-4, atol=2e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# -- make_hier_mesh: shape matrix --------------------------------------------


def test_hier_mesh_shapes():
    for slices, ici in [(2, 4), (4, 2), (8, 1)]:
        mesh = make_hier_mesh(slices)
        assert mesh.axis_names == HIER_DATA_AXES
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) \
            == {"dcn": slices, "ici": ici}
        assert is_hier_mesh(mesh)
    assert not is_hier_mesh(make_mesh(("data",)))


def test_hier_mesh_device_subset_2x2():
    mesh = make_hier_mesh(2, devices=jax.devices()[:4])
    assert mesh.devices.shape == (2, 2)
    # Emulated slices are contiguous blocks of the given order — the
    # data-major layout every sharder here assumes.
    assert [d.id for d in mesh.devices.flat] == [0, 1, 2, 3]


def test_hier_mesh_model_axes_nest_inside_a_slice():
    mesh = make_hier_mesh(2, extra_axes=("model",), extra_shape=(2,))
    assert mesh.axis_names == ("dcn", "ici", "model")
    assert mesh.devices.shape == (2, 2, 2)
    # The model group is the innermost (fastest-varying) block: both of
    # a group's chips come from one slice.
    for s in range(2):
        slice_ids = {d.id for d in mesh.devices[s].flat}
        assert slice_ids == set(range(s * 4, s * 4 + 4))


def test_hier_mesh_rejection_matrix():
    with pytest.raises(ValueError, match="split into"):
        make_hier_mesh(3)
    with pytest.raises(ValueError, match=">= 1"):
        make_hier_mesh(0)
    with pytest.raises(ValueError, match="straddle"):
        # 4 slices of 2 chips cannot nest a width-4 model group.
        make_hier_mesh(4, extra_axes=("model",), extra_shape=(4,))
    with pytest.raises(ValueError, match="collides"):
        make_hier_mesh(2, extra_axes=("dcn",), extra_shape=(2,))
    with pytest.raises(ValueError, match="pair up"):
        make_hier_mesh(2, extra_axes=("model",), extra_shape=())
    with pytest.raises(ValueError, match="slice topology"):
        make_hier_mesh()  # no env, no slice_index: nothing to build on


def test_env_resolution(monkeypatch):
    monkeypatch.setenv(DCN_SLICES_ENV, "2")
    assert infer_dcn_slices() == 2
    mesh = make_hier_mesh()
    assert mesh.devices.shape == (2, 4)
    monkeypatch.setenv(DCN_SLICES_ENV, "nope")
    with pytest.raises(ValueError, match=DCN_SLICES_ENV):
        infer_dcn_slices()
    monkeypatch.delenv(DCN_SLICES_ENV)
    assert infer_dcn_slices() == 1  # CPU devices report no slice_index


def _fake(slice_index=None, pid=0, did=0):
    return SimpleNamespace(slice_index=slice_index, process_index=pid,
                           id=did)


def test_slice_blocks_orders_real_topology_slice_major():
    devs = [_fake(1, did=2), _fake(0, did=0), _fake(1, did=3),
            _fake(0, did=1)]
    ordered = _slice_blocks(devs, 2)
    assert [d.slice_index for d in ordered] == [0, 0, 1, 1]
    with pytest.raises(ValueError, match="distinct slice_index"):
        _slice_blocks(devs, 4)  # only 2 real slices exist
    uneven = [_fake(0), _fake(0), _fake(0), _fake(1)]
    with pytest.raises(ValueError, match="unequal slice sizes"):
        _slice_blocks(uneven, 2)


def test_validate_dcn_slices_catches_real_topology_mismatch():
    """The pre-construction validation cli.py runs: a slice count that
    DIVIDES the device count but contradicts the real slice topology
    must still be rejected (or, under an elastic rebuild, trigger the
    flat fallback) — not surface as a raw traceback at mesh build."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import (
        validate_dcn_slices,
    )

    devs = [_fake(i // 4, did=i) for i in range(8)]  # 2 real slices x 4
    validate_dcn_slices(2, devs)  # matches: fine
    with pytest.raises(ValueError, match="distinct slice_index"):
        validate_dcn_slices(4, devs)  # divides 8, contradicts topology
    with pytest.raises(ValueError, match="split into"):
        validate_dcn_slices(3, devs)
    validate_dcn_slices(2)  # the real (emulation-free) world: 8 CPU devs


# -- the composed data axis ---------------------------------------------------


def _grid(shape, proc_of_flat):
    n = int(np.prod(shape))
    devs = np.array(
        [SimpleNamespace(process_index=proc_of_flat(i)) for i in range(n)],
        dtype=object,
    ).reshape(shape)
    return devs


def test_data_replica_coords_composed_axis():
    # hier (dcn=2, ici=2) over 2 hosts, one slice per host: each host
    # covers a contiguous half of the composed data axis.
    fake = SimpleNamespace(axis_names=("dcn", "ici"),
                           devices=_grid((2, 2), lambda i: i // 2))
    assert data_replica_coords(fake, process_index=0) == (2, 0)
    assert data_replica_coords(fake, process_index=1) == (2, 1)
    # 4 single-device hosts: identity on the composed axis.
    fake4 = SimpleNamespace(axis_names=("dcn", "ici"),
                            devices=_grid((2, 2), lambda i: i))
    assert [data_replica_coords(fake4, process_index=p)
            for p in range(4)] == [(4, 0), (4, 1), (4, 2), (4, 3)]


def test_data_replica_coords_hier_model_axis():
    # (dcn=2, ici=1, model=2) over 2 hosts: a host's two chips differ
    # only along 'model' — one data replica per host.
    fake = SimpleNamespace(axis_names=("dcn", "ici", "model"),
                           devices=_grid((2, 1, 2), lambda i: i // 2))
    assert data_replica_coords(fake, process_index=0) == (2, 0)
    assert data_replica_coords(fake, process_index=1) == (2, 1)


def test_data_replica_coords_hier_real_mesh_single_process():
    assert data_replica_coords(make_hier_mesh(2), process_index=0) == (1, 0)


def test_data_sharding_and_resolve_on_hier_mesh():
    hier = make_hier_mesh(2)
    flat = make_mesh(("data",))
    assert resolve_data_axis(hier) == HIER_DATA_AXES
    assert resolve_data_axis(flat) == "data"
    assert resolve_data_axis(hier, "model") == "model"
    assert data_sharding(hier).spec == P(HIER_DATA_AXES)
    assert data_sharding(flat).spec == P("data")


def test_device_slice_map_emulated(monkeypatch):
    devs = jax.devices()
    assert device_slice_map(devs) is None  # no topology at all
    monkeypatch.setenv(DCN_SLICES_ENV, "2")
    assert device_slice_map(devs) == [0, 0, 0, 0, 1, 1, 1, 1]
    assert device_slice_map(devs[2:6]) == [0, 0, 1, 1]
    monkeypatch.setenv(DCN_SLICES_ENV, "3")  # does not divide: no map
    assert device_slice_map(devs) is None


def test_chaos_env_name_pinned():
    # tools/chaos.py spells the env out to stay jax-import-free.
    from tools import chaos

    assert chaos.DCN_SLICES_ENV == DCN_SLICES_ENV


def test_chaos_kill_slice_composes_fault_specs(monkeypatch):
    """``chaos.py --kill-slice S`` = SIGKILL every host of emulated
    slice S: the env + multi-fault composition the slice-loss twin in
    tests/test_elastic_chaos.py drives directly."""
    from tools import chaos

    # Register the keys main() mutates so monkeypatch restores them.
    monkeypatch.setenv("TPUMNIST_FAULT", "sentinel")
    monkeypatch.setenv(DCN_SLICES_ENV, "sentinel")
    monkeypatch.setenv("TPUMNIST_AGREEMENT_TIMEOUT", "300")
    captured = {}

    def fake_supervise(nprocs, cli_args, **kw):
        captured["nprocs"] = nprocs
        captured["fault"] = os.environ.get("TPUMNIST_FAULT")
        captured["slices"] = os.environ.get(DCN_SLICES_ENV)
        return 0

    monkeypatch.setattr(chaos, "supervise", fake_supervise)
    rc = chaos.main(["--elastic", "--dcn-slices", "2", "--kill-slice", "1",
                     "--nprocs", "4", "--", "--dataset", "synthetic"])
    assert rc == 0 and captured["nprocs"] == 4
    assert captured["slices"] == "2"
    # Slice 1 of 2 over 4 hosts = hosts 2 and 3, mid-epoch kills.
    assert captured["fault"] == "train_step:2:kill:5,train_step:3:kill:5"
    with pytest.raises(SystemExit, match="elastic"):
        chaos.main(["--kill-slice", "0", "--dcn-slices", "2"])
    with pytest.raises(SystemExit, match="divide"):
        chaos.main(["--elastic", "--dcn-slices", "3", "--nprocs", "4"])
    with pytest.raises(SystemExit, match="not one of"):
        chaos.main(["--elastic", "--dcn-slices", "2", "--kill-slice", "2",
                    "--nprocs", "4"])


# -- the DCN bucket plan budgets SHARD bytes ---------------------------------


def test_dcn_bucket_plan_budgets_shard_bytes():
    class _Leaf:
        def __init__(self, shape):
            self.shape = shape
            self.dtype = np.dtype(np.float32)

    # Two 1-MiB leaves: full-size they need a bucket each at 1 MiB, but
    # their 1/4 shards pack together into one 1-MiB DCN bucket.
    leaves = [_Leaf((1024, 256)), _Leaf((512, 512))]
    dims = _shard_dims(leaves, 4, "ici")
    from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
        bucket_plan,
    )

    assert len(bucket_plan(leaves, 1.0)) == 2
    assert len(_dcn_bucket_plan(leaves, dims, 4, 1.0)) == 1


# -- trajectory equality: 2x2 hier vs the flat 4-mesh ------------------------


@pytest.mark.parametrize("sharding", ["plain", "zero1", "zero3"])
def test_hier_propagation_matches_flat(sharding):
    """The acceptance matrix's propagation half: the SAME GSPMD step on
    the 2x2 emulated hierarchy and on the flat 4-mesh, 3 steps,
    params/moments/metrics equal (fp-order tolerance)."""
    devs = jax.devices()[:4]
    flat = make_mesh(("data",), devices=devs)
    hier = make_hier_mesh(2, devices=devs)
    model = get_model("linear", compute_dtype=jnp.float32)

    def build(mesh):
        st = create_train_state(model, jax.random.key(0))
        if sharding == "plain":
            return st, None
        return shard_state_zero(
            st, mesh, level=3 if sharding == "zero3" else 1)

    f_state, f_sh = build(flat)
    h_state, h_sh = build(hier)
    f_step = make_train_step(flat, state_sharding=f_sh)
    h_step = make_train_step(hier, state_sharding=h_sh)
    for i in range(3):
        b = _batch(i)
        f_state, fm = f_step(f_state, b)
        h_state, hm = h_step(h_state, b)
    np.testing.assert_allclose(float(fm.loss_sum), float(hm.loss_sum),
                               rtol=1e-5)
    assert float(fm.count) == float(hm.count)
    _assert_trees_close(f_state.params, h_state.params)
    _assert_trees_close(f_state.opt_state, h_state.opt_state)


@pytest.mark.parametrize("level", [1, 3])
def test_two_tier_overlap_matches_flat_overlap_and_propagation(level):
    """THE acceptance equivalence: the two-tier overlapped schedule on
    the 2x2 emulated hierarchy vs the flat 4-device overlap path vs the
    flat propagation path — independent per-tier buckets exercised
    (bucket_mb_dcn != bucket_mb), same trajectory everywhere."""
    devs = jax.devices()[:4]
    flat = make_mesh(("data",), devices=devs)
    hier = make_hier_mesh(2, devices=devs)
    model = get_model("linear", compute_dtype=jnp.float32)

    prop, prop_sh = shard_state_zero(
        create_train_state(model, jax.random.key(0)), flat, level=level)
    prop_step = make_train_step(flat, state_sharding=prop_sh)

    fo, _ = shard_state_zero(
        create_train_state(model, jax.random.key(0)), flat, level=level)
    fo_step = make_overlap_train_step(fo, flat, level=level, bucket_mb=0.5)
    fo_g = make_param_gather(flat)(fo.params) if level == 3 else None

    tt, _ = shard_state_zero(
        create_train_state(model, jax.random.key(0)), hier, level=level)
    tt_step = make_overlap_train_step(tt, hier, level=level, bucket_mb=0.5,
                                      bucket_mb_dcn=0.125)
    tt_g = make_param_gather(hier)(tt.params) if level == 3 else None

    for i in range(3):
        b = _batch(i)
        prop, pm = prop_step(prop, b)
        if level == 3:
            fo, fo_g, fom = fo_step(fo, fo_g, b)
            tt, tt_g, ttm = tt_step(tt, tt_g, b)
        else:
            fo, fom = fo_step(fo, b)
            tt, ttm = tt_step(tt, b)
    np.testing.assert_allclose(float(pm.loss_sum), float(ttm.loss_sum),
                               rtol=1e-5)
    np.testing.assert_allclose(float(fom.loss_sum), float(ttm.loss_sum),
                               rtol=1e-5)
    assert float(pm.count) == float(ttm.count) == float(fom.count)
    _assert_trees_close(prop.params, tt.params)
    _assert_trees_close(fo.params, tt.params)
    _assert_trees_close(prop.opt_state, tt.opt_state)


def test_two_tier_scan_epoch_and_carry_invariant():
    """ZeRO-3 two-tier through the scan epoch: trajectory equal to the
    flat overlap epoch, and the carried gathered copy leaving the epoch
    IS allgather(shards) — the invariant the Trainer relies on."""
    devs = jax.devices()[:4]
    flat = make_mesh(("data",), devices=devs)
    hier = make_hier_mesh(2, devices=devs)
    model = get_model("linear", compute_dtype=jnp.float32)
    r = np.random.default_rng(7)
    batches = {
        "image": jnp.asarray(r.normal(size=(4, 64, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(r.integers(0, 10, size=(4, 64)), jnp.int32),
    }

    f, _ = shard_state_zero(
        create_train_state(model, jax.random.key(1)), flat, level=3)
    f_epoch = make_overlap_train_epoch(f, flat, level=3, bucket_mb=0.5)
    f_g = make_param_gather(flat)(f.params)
    f, f_g, fm = f_epoch(f, f_g, batches)

    h, _ = shard_state_zero(
        create_train_state(model, jax.random.key(1)), hier, level=3)
    h_epoch = make_overlap_train_epoch(h, hier, level=3, bucket_mb=0.5,
                                       bucket_mb_dcn=0.25)
    h_g = make_param_gather(hier)(h.params)
    copies = jax.tree_util.tree_map(jnp.copy, batches)
    h, h_g, hm = h_epoch(h, h_g, copies)

    np.testing.assert_allclose(float(fm.loss_sum), float(hm.loss_sum),
                               rtol=1e-5)
    _assert_trees_close(f.params, h.params)
    full = make_param_gather(hier)(h.params)
    for a, c in zip(jax.tree_util.tree_leaves(full),
                    jax.tree_util.tree_leaves(h_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_hier_state_layout_shards_over_ici_only():
    """The hierarchical ZeRO layout: shard specs name 'ici' alone —
    replicated across slices (the 2004.13336 multi-pod partition), so
    only 1/ici_size owner shards ever cross DCN."""
    hier = make_hier_mesh(2)
    model = get_model("linear", compute_dtype=jnp.float32)
    state, sharding = shard_state_zero(
        create_train_state(model, jax.random.key(0)), hier, level=3)
    axes_used = set()
    for ns in jax.tree_util.tree_leaves(sharding):
        for entry in ns.spec:
            if entry is not None:
                axes_used.add(entry)
    assert axes_used == {"ici"}


# -- cli: end to end ----------------------------------------------------------


def _cli_args(tmp_path, extra, epochs=2):
    from pytorch_distributed_mnist_tpu.cli import build_parser

    return build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear",
        "--epochs", str(epochs),
        "--batch-size", "64", "--synthetic-train-size", "256",
        "--synthetic-test-size", "128", "--seed", "0",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
    ] + extra)


def test_cli_dcn_slices_zero_overlap_matches_flat(tmp_path):
    """--dcn-slices 2 end to end under --zero-overlap: the full driver's
    history equals the flat run's, per-tier buckets wired through."""
    from pytorch_distributed_mnist_tpu.cli import run

    flat = run(_cli_args(tmp_path / "a",
                         ["--optimizer-sharding", "zero1",
                          "--zero-overlap"]))
    hier = run(_cli_args(tmp_path / "b",
                         ["--optimizer-sharding", "zero1", "--zero-overlap",
                          "--dcn-slices", "2",
                          "--zero-bucket-mb-dcn", "1"]))
    assert "train_epoch_zero_overlap" in hier["compile_stats"]["programs"]
    for hf, hh in zip(flat["history"], hier["history"]):
        np.testing.assert_allclose(hf["train_loss"], hh["train_loss"],
                                   rtol=1e-4)
        np.testing.assert_allclose(hf["test_acc"], hh["test_acc"],
                                   rtol=1e-6)


@pytest.mark.slow
def test_cli_dcn_slices_zero3_stepwise_matches_flat(tmp_path):
    from pytorch_distributed_mnist_tpu.cli import run

    flat = run(_cli_args(tmp_path / "a",
                         ["--optimizer-sharding", "zero3", "--zero-overlap",
                          "--trainer-mode", "stepwise"]))
    hier = run(_cli_args(tmp_path / "b",
                         ["--optimizer-sharding", "zero3", "--zero-overlap",
                          "--trainer-mode", "stepwise",
                          "--dcn-slices", "2"]))
    for hf, hh in zip(flat["history"], hier["history"]):
        np.testing.assert_allclose(hf["train_loss"], hh["train_loss"],
                                   rtol=1e-4)


def test_cli_hier_checkpoint_loads_on_flat_world(tmp_path):
    """'Same checkpoints load both ways': 2 epochs trained on the
    hierarchical mesh, then a FLAT resume for epoch 3 — the flat world
    loads the hier-written checkpoint without ceremony and the resumed
    epoch's metrics match an uninterrupted flat run's at the suite's
    standard cross-path tolerance (the hier and flat meshes reduce in
    different fp orders, so bitwise equality is not the contract)."""
    from pytorch_distributed_mnist_tpu.cli import run

    full = run(_cli_args(tmp_path / "flat",
                         ["--optimizer-sharding", "zero1", "--resume",
                          "auto"], epochs=3))
    run(_cli_args(tmp_path / "x",
                  ["--optimizer-sharding", "zero1", "--resume", "auto",
                   "--dcn-slices", "2"], epochs=2))
    resumed = run(_cli_args(tmp_path / "x",
                            ["--optimizer-sharding", "zero1",
                             "--resume", "auto"], epochs=3))
    assert resumed["start_epoch"] == 2 and resumed["epochs_run"] == 1
    row_full, row_res = full["history"][2], resumed["history"][0]
    assert row_res["epoch"] == 2
    for key in ("train_loss", "train_acc", "test_loss", "test_acc"):
        np.testing.assert_allclose(row_res[key], row_full[key], rtol=2e-4,
                                   err_msg=key)


def test_cli_flat_checkpoint_loads_on_hier_world(tmp_path):
    """The reverse direction: a FLAT-trained checkpoint resumes on the
    hierarchical mesh (the elastic grow-into-multi-slice shape)."""
    from pytorch_distributed_mnist_tpu.cli import run

    run(_cli_args(tmp_path / "x",
                  ["--optimizer-sharding", "zero1", "--resume", "auto"],
                  epochs=2))
    resumed = run(_cli_args(tmp_path / "x",
                            ["--optimizer-sharding", "zero1",
                             "--resume", "auto", "--dcn-slices", "2"],
                            epochs=3))
    assert resumed["start_epoch"] == 2 and resumed["epochs_run"] == 1


@pytest.mark.parametrize("extra, match", [
    (["--dcn-slices", "3"], "split into"),
    (["--dcn-slices", "-1"], "dcn-slices"),
    (["--dcn-slices", "2", "--trainer-mode", "explicit"], "explicit"),
    (["--dcn-slices", "2", "--loss", "fused"], "fused"),
    (["--dcn-slices", "2", "--model", "vit", "--pipeline-stages", "2"],
     "pipeline"),
    (["--dcn-slices", "2", "--model", "vit", "--sequence-parallel", "2",
      "--patch-size", "7"], "sequence-parallel"),
    (["--dcn-slices", "2", "--model", "moe_mlp", "--expert-parallel", "4",
      "--moe-dispatch", "capacity"], "capacity"),
    (["--dcn-slices", "4", "--model", "moe_mlp", "--expert-parallel", "4"],
     "straddle"),
    (["--dcn-slices", "2", "--model", "vit", "--tensor-parallel", "2",
      "--attention", "flash"], "flash"),
    (["--zero-bucket-mb-dcn", "1"], "zero-overlap"),
    (["--optimizer-sharding", "zero1", "--zero-overlap",
      "--zero-bucket-mb-dcn", "-1"], "zero-bucket-mb-dcn"),
])
def test_cli_dcn_rejection_matrix(tmp_path, extra, match):
    from pytorch_distributed_mnist_tpu.cli import run

    with pytest.raises(SystemExit, match=match):
        run(_cli_args(tmp_path, extra))


@pytest.mark.slow
def test_cli_dcn_slices_tensor_parallel_matches_flat(tmp_path):
    """TP pins to the ICI tier: the GSPMD rule table composes with the
    hierarchical mesh and the trajectory equals the flat TP run."""
    from pytorch_distributed_mnist_tpu.cli import run

    flat = run(_cli_args(tmp_path / "a",
                         ["--model", "vit", "--tensor-parallel", "2"]))
    hier = run(_cli_args(tmp_path / "b",
                         ["--model", "vit", "--tensor-parallel", "2",
                          "--dcn-slices", "2"]))
    for hf, hh in zip(flat["history"], hier["history"]):
        np.testing.assert_allclose(hf["train_loss"], hh["train_loss"],
                                   rtol=1e-4)


# -- analyzer cleanliness -----------------------------------------------------


@pytest.mark.lint
def test_mesh_and_zero_overlap_modules_clean_under_analyzer():
    """The satellite pin: the hierarchical mesh machinery and the
    two-tier schedule stay clean under the checkers whose invariants
    they most plausibly violate."""
    from tools.analyzer import run_analysis

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(repo, "pytorch_distributed_mnist_tpu", "parallel")
    result = run_analysis(
        [os.path.join(pkg, "mesh.py"), os.path.join(pkg, "zero_overlap.py")],
        checkers=["collective-symmetry", "trace-purity",
                  "recompile-hazard", "lock-discipline"],
    )
    assert not result.findings, [
        f"{f.path}:{f.line} [{f.checker}] {f.message}"
        for f in result.findings
    ]
