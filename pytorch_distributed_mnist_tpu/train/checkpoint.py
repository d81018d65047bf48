"""Checkpoint save / resume.

Schema parity with the reference's richest auxiliary subsystem
(``/root/reference/multi_proc_single_gpu.py:249-255, 263-271, 197-214``):

- checkpoint dict ``{epoch: epoch+1, state_dict, best_acc, optimizer}``
  becomes ``{epoch, best_acc}`` metadata + the flattened
  ``{params, opt_state, step}`` leaf arrays (and ``buffers``, where the
  state carries variables that no gradient moves);
- one file per epoch (``checkpoint_{epoch}.npz``) plus a ``model_best``
  copy on improvement (``:267-271``; every epoch's file retained, no GC,
  same as the reference);
- only process 0 writes (``:248-249``);
- restore maps the saved arrays onto the *current* mesh: the analog of
  ``torch.load(map_location=device)`` (``:202``) is ``device_put`` with each
  leaf's target sharding, which is restore-time resharding — so a run
  trained on 8 chips restores for single-chip ``--evaluate``
  (BASELINE.json configs 3-4);
- writes are atomic (tmp file + ``os.replace``), which the reference is not
  — a rank killed mid-``torch.save`` leaves a truncated file there.

Format: ``.npz`` (zip of npy arrays) + a JSON sidecar inside the archive —
no pickle, no framework-versioned opaque bytes; leaves are matched to a
*template* state at restore time, the same contract as
``load_state_dict`` needing a constructed model (``:209``).

Two layouts, chosen automatically at save time:

- **npz file** (``checkpoint_{e}.npz``) when every leaf is fully
  addressable from this process — single-host runs, and multi-host DP
  where params/moments are replicated. One process-0 write, as the
  reference does (``:248-249``).
- **sharded directory** (``checkpoint_{e}.ckpt/``) when any leaf spans
  non-addressable devices (multi-host TP/EP/ZeRO states, where
  ``np.asarray(leaf)`` would raise): every process writes only the shards
  it owns (``shard.replica_id == 0`` de-dupes replicas) into its own
  ``shards_p{pid}.npz`` + slice-index JSON, process 0 writes the global
  ``meta.json``, and the directory is atomically published after a
  cross-host barrier. Restore stitches the global array from the slice
  index and redistributes onto the template's shardings — so the layout
  round-trips across different mesh shapes, same as the npz path.

Cross-world resharding contract: BOTH layouts restore onto any world —
any process count, any mesh, any optimizer-sharding level the template
was built with — because restore always goes through full host arrays
and the template's own shardings (``_restore_onto_template``; for ZeRO
states the specs are ``parallel/zero.py::zero_state_sharding``'s, so a
resumed state is bit-identical to a fresh shard of the gathered
arrays). This is what lets the elastic runtime (``runtime/elastic.py``)
resume a checkpoint saved at world size W on the W' survivors of a host
loss, and a serve pool reload across topologies. The saving world is
stamped in meta (``checkpoint_world``) as inspectable provenance;
``tests/test_reshard.py`` pins the (W, W') round-trip matrix.
"""

from __future__ import annotations

import io
import json
import os
import re
import shutil
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from pytorch_distributed_mnist_tpu.runtime.supervision import maybe_fault
from pytorch_distributed_mnist_tpu.utils.watchdog import retry_with_backoff

CHECKPOINT_DIR = "checkpoints"

# Quarantine suffix for corrupt checkpoints (resume-time rename); the
# `_epoch_checkpoints` pattern can never match a quarantined name, so a
# quarantined file is invisible to resolution and pruning alike.
CORRUPT_SUFFIX = ".corrupt"


def _leaves_with_names(tree: Any):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _state_tree(state) -> Dict[str, Any]:
    """What a checkpoint holds of a state. ``buffers`` (the state no
    gradient moves, ``train/state.py``) is a key only where the state has
    them, so that every other state writes the leaves, names and bytes it
    always wrote."""
    tree = {"params": state.params, "opt_state": state.opt_state,
            "step": state.step}
    if getattr(state, "buffers", None) is not None:
        tree["buffers"] = state.buffers
    return tree


def _world_stamp() -> Dict[str, int]:
    """The saving world's shape, stamped into checkpoint meta (both
    layouts) as provenance: the elastic resume path and serve boot can
    see — by meta inspection, before any array bytes move — that a
    checkpoint was saved at a different world size and will be
    re-sharded onto this one. The restore path never *requires* a
    match: ``_restore_onto_template`` re-shards any layout onto any
    process count and mesh (the cross-world contract
    ``tests/test_reshard.py`` pins)."""
    return {"processes": int(jax.process_count()),
            "devices": int(jax.device_count())}


def _npz_saveable(leaf: Any) -> bool:
    """True when ``np.asarray(leaf)`` works on this process: the leaf is
    fully addressable (single host) or fully replicated (multi-host DP —
    every host holds the whole value). Only genuinely cross-host-sharded
    leaves (multi-host TP/EP/ZeRO) need the sharded directory layout."""
    return bool(getattr(leaf, "is_fully_addressable", True)
                or getattr(leaf, "is_fully_replicated", False))


def save_checkpoint(
    state,
    *,
    epoch: int,
    best_acc: float,
    is_best: bool,
    directory: str = CHECKPOINT_DIR,
    process_index: Optional[int] = None,
    layout: Optional[str] = None,
    keep_last: int = 0,
    parallel_layout: Optional[Dict[str, Any]] = None,
    publish: Optional[str] = None,
    chunk_mb: float = 4.0,
) -> Optional[str]:
    """Write ``checkpoint_{epoch}.npz`` (+ best copy); returns the path.

    ``epoch`` is stored as ``epoch + 1`` — the reference's convention
    (``:251``) so resume continues at the *next* epoch (``:204``). Only
    process 0 writes (``:248-249``); other processes return None — except
    when a leaf spans non-addressable devices (multi-host sharded state),
    where every process contributes its own shards to a ``.ckpt``
    directory instead.

    ``parallel_layout`` stamps the run's training parallelism into the
    checkpoint meta (``{"tensor": w, "expert": w, "sequence": w,
    "pipeline": w}`` widths; the CLI passes its flag values) — the
    provenance the serve boot/reload layout gate
    (``serve/programs.py::check_checkpoint_layout``) reads so an
    expert/tensor-trained checkpoint cannot be silently served under a
    mismatched ``--serve-mode``. ``None`` (library callers, old files)
    writes no field and the gate passes everything.
    """
    if layout not in (None, "npz", "sharded"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    if publish not in (None, "full", "delta"):
        raise ValueError(f"unknown publish mode {publish!r}")
    if publish == "delta":
        # Content-addressed delta publish (``--publish delta``): chunks
        # absent from the store + an atomic manifest INSTEAD of the npz
        # file. Resume, watcher resolution, and pruning all already
        # treat the manifest as a first-class checkpoint via the shared
        # ``_epoch_checkpoints`` pattern. An explicit sharded-layout
        # request is contradictory (the manifest replaces the npz
        # layout) and cross-host sharded states are rejected loudly
        # inside ``publish_state`` — both route the caller to: save the
        # sharded layout, then convert with ``publish_from_checkpoint``.
        if layout == "sharded":
            raise ValueError(
                "--publish delta replaces the npz layout and cannot "
                "write layout='sharded'; save the sharded layout and "
                "convert via publish_from_checkpoint")
        from pytorch_distributed_mnist_tpu.distrib.publish import (
            publish_state,
        )

        return publish_state(
            state, epoch=epoch, best_acc=best_acc, directory=directory,
            chunk_mb=chunk_mb, is_best=is_best, keep_last=keep_last,
            process_index=process_index, parallel_layout=parallel_layout)
    pid = jax.process_index() if process_index is None else process_index
    named = _leaves_with_names(_state_tree(state))
    if layout == "sharded" or (
        layout is None and not all(_npz_saveable(v) for _, v in named)
    ):
        return _save_sharded(
            named, epoch=epoch, best_acc=best_acc, is_best=is_best,
            directory=directory, pid=pid, keep_last=keep_last,
            parallel_layout=parallel_layout,
        )
    if pid != 0:
        return None
    os.makedirs(directory, exist_ok=True)
    payload: Dict[str, np.ndarray] = {f"leaf_{i}": np.asarray(v) for i, (_, v) in enumerate(named)}
    meta = {
        "epoch": epoch + 1,
        "best_acc": float(best_acc),
        "leaf_names": [k for k, _ in named],
        "format_version": 1,
        "world": _world_stamp(),
    }
    if parallel_layout is not None:
        meta["parallel_layout"] = dict(parallel_layout)
    buf = io.BytesIO()
    np.savez(buf, __meta__=np.frombuffer(json.dumps(meta).encode(), np.uint8), **payload)
    path = os.path.join(directory, f"checkpoint_{epoch}.npz")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(buf.getvalue())
    os.replace(tmp, path)  # atomic publish
    if is_best:
        best = os.path.join(directory, "model_best.npz")
        shutil.copyfile(path, best + ".tmp")
        os.replace(best + ".tmp", best)
    prune_checkpoints(directory, keep_last)
    return path


def _shard_slices(leaf, shard) -> Tuple[list, list]:
    """Normalize a shard's index into explicit [start], [stop] lists."""
    starts, stops = [], []
    for sl, dim in zip(shard.index, leaf.shape):
        a, b, _ = sl.indices(dim)
        starts.append(int(a))
        stops.append(int(b))
    return starts, stops


def _sharded_prepare(directory: str, epoch: int, pid: int) -> Tuple[str, str]:
    """Phase 1 (main thread, collective): clean + create the tmp dir.

    Returns ``(tmp, final)``. Contains a cross-host collective, so it
    must run on the thread that owns the device (never a writer thread).
    Process 0's local filesystem work is wrapped in the phase agreement:
    a cleanup failure fails every host together rather than process 0
    raising alone while its peers block in the synchronization — the
    agreement collective doubles as the nobody-writes-into-a-dir-
    being-rm'd barrier. Creating each host's own view of ``tmp`` is left
    to the callers' guarded produce phase for the same reason."""
    maybe_fault("ckpt_prepare")
    final = os.path.join(directory, f"checkpoint_{epoch}.ckpt")
    tmp = final + ".tmp"  # same deterministic name on every process
    err: Optional[BaseException] = None
    if pid == 0:
        try:
            # A crashed earlier attempt may have left stale shard files
            # here; publishing those alongside fresh ones would silently
            # corrupt the restore (stale index records overwrite
            # freshly-stitched regions).
            if os.path.isdir(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
        except Exception as exc:
            err = exc
    _agree_phase_ok(err, epoch, "prepare",
                    f"tmp dir {tmp} could not be prepared")
    return tmp, final


def _sharded_collect(named, pid: int) -> Tuple[Dict[str, np.ndarray], list]:
    """Phase 2 (main thread, device reads): host copies of OWNED shards.

    Ownership = ``shard.replica_id == 0``: exactly one device globally
    holds replica 0 of each distinct shard, so replicated leaves (and the
    replicated dims of partially-sharded ones) are written once, not once
    per host. ``np.asarray(shard.data)`` is a D2H copy, so the returned
    payload is a consistent snapshot — the train loop may donate the
    device buffers the moment this returns."""
    maybe_fault("ckpt_collect")
    payload: Dict[str, np.ndarray] = {}
    index = []
    for i, (_, leaf) in enumerate(named):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:  # plain host array (e.g. python scalar leaf)
            if pid == 0:
                key = f"leaf{i}_s0"
                arr = np.asarray(leaf)
                payload[key] = arr
                index.append({"leaf": i, "key": key,
                              "start": [0] * arr.ndim,
                              "stop": list(arr.shape)})
            continue
        for j, shard in enumerate(shards):
            if shard.replica_id != 0:
                continue
            key = f"leaf{i}_s{j}"
            payload[key] = np.asarray(shard.data)
            starts, stops = _shard_slices(leaf, shard)
            index.append({"leaf": i, "key": key, "start": starts,
                          "stop": stops})
    return payload, index


def _sharded_meta(named, epoch: int, best_acc: float,
                  parallel_layout: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    meta = {
        "epoch": epoch + 1,
        "best_acc": float(best_acc),
        "leaf_names": [k for k, _ in named],
        "global_shapes": [list(np.shape(v)) for _, v in named],
        "dtypes": [np.dtype(getattr(v, "dtype", np.float32)).name
                   for _, v in named],
        "format_version": 2,
        "world": _world_stamp(),
    }
    if parallel_layout is not None:
        meta["parallel_layout"] = dict(parallel_layout)
    return meta


def _sharded_write_files(tmp: str, pid: int, payload, index,
                         meta: Optional[Dict[str, Any]]) -> None:
    """Phase 3 (any thread): pure file I/O, no device or collective use —
    the part the AsyncCheckpointer overlaps with the next epoch."""
    maybe_fault("ckpt_write")
    shard_file = f"shards_p{pid:05d}.npz"
    if payload:
        with open(os.path.join(tmp, shard_file), "wb") as f:
            np.savez(f, **payload)
    with open(os.path.join(tmp, f"index_p{pid:05d}.json"), "w") as f:
        json.dump({"file": shard_file if payload else None,
                   "shards": index}, f)
    if meta is not None:  # pid 0 only
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)


def _publish_dir(tmp: str, final: str, directory: str, epoch: int,
                 is_best: bool, keep_last: int) -> None:
    """Process 0's publish body: shared-fs check, atomic rename, best
    copy, GC. Factored out so the multi-process fault tests can inject a
    failure here and pin that it fails EVERY host (see _sharded_publish).
    """
    # Shared-filesystem check: every host's index file must be visible
    # here, or the published checkpoint would be missing their shards
    # (and resume would diverge: host 0 errors, others start fresh).
    missing = [
        p for p in range(jax.process_count())
        if not os.path.isfile(os.path.join(tmp, f"index_p{p:05d}.json"))
    ]
    if missing:
        raise RuntimeError(
            f"sharded checkpoint save: index files from processes "
            f"{missing} are not visible in {tmp} — --checkpoint-dir "
            f"must be a filesystem shared by all hosts"
        )
    if os.path.isdir(final):
        shutil.rmtree(final)

    # Atomic publish of the complete directory. The rename is the one
    # retry-safe step on a network filesystem (transient ESTALE/EIO on a
    # busy NFS export): bounded backoff+jitter, because failing here
    # aborts EVERY host via the publish agreement while a one-line retry
    # publishes a checkpoint that is already fully on disk.
    from pytorch_distributed_mnist_tpu.utils.profiling import failure_events

    def _replace_once() -> None:
        try:
            os.replace(tmp, final)
        except OSError:
            if os.path.isdir(final) and not os.path.exists(tmp):
                # NFS lost-reply duplicate: the server performed the
                # rename but the client's reply was lost, so the retry
                # sees ENOENT for tmp. The publish already landed —
                # treating this as failure would abort EVERY host over a
                # checkpoint that is intact on disk.
                return
            raise

    retry_with_backoff(
        _replace_once,
        attempts=3, retry_on=(OSError,),
        on_retry=lambda attempt, exc, delay: failure_events.record(
            "publish_retry",
            f"rename to {final} attempt {attempt} failed ({exc!r}); "
            f"retrying in {delay:.2f}s"),
    )
    try:
        if is_best:
            best = os.path.join(directory, "model_best.ckpt")
            best_tmp = best + ".copy_tmp"
            if os.path.isdir(best_tmp):
                shutil.rmtree(best_tmp)
            shutil.copytree(final, best_tmp)
            if os.path.isdir(best):
                shutil.rmtree(best)
            os.replace(best_tmp, best)
        prune_checkpoints(directory, keep_last)
    except Exception as exc:
        # The rename above already landed: say so, or the phase-failure
        # message would misdirect a postmortem into discarding (or
        # re-running) a checkpoint that IS valid on disk.
        raise RuntimeError(
            f"checkpoint {final} WAS published, but a post-publish step "
            f"(best copy / prune) failed: {exc!r}"
        ) from exc


def _sharded_publish(tmp: str, final: str, directory: str, epoch: int,
                     is_best: bool, keep_last: int, pid: int) -> str:
    """Phase 4 (main thread, collective): barrier until every host's
    files are on disk, then process 0 atomically publishes the dir.

    ``directory`` must be a filesystem shared by all hosts (the same
    assumption the reference makes for every rank loading rank 0's file,
    ``:202``); process 0 verifies that after the write barrier by checking
    every host's index file is visible before publishing. Process 0's
    publish outcome is AGREED before anyone proceeds: that RuntimeError
    (a real misconfiguration a user can hit) previously raised on
    process 0 alone while every peer blocked in the trailing barrier
    forever. The agreement collective doubles as the
    no-reader-races-a-half-published-dir barrier.

    ORDERING CONTRACT: callers must run the write-phase
    ``_agree_phase_ok`` immediately before this function (both call
    sites do) — that agreement is the all-shard-files-are-on-disk
    barrier, so no extra collective runs here before process 0 checks
    visibility."""
    maybe_fault("ckpt_publish")
    err: Optional[BaseException] = None
    if pid == 0:
        try:
            _publish_dir(tmp, final, directory, epoch, is_best, keep_last)
        except Exception as exc:
            err = exc
    _agree_phase_ok(err, epoch, "publish",
                    f"checkpoint dir {final} may not have been published "
                    f"— see the failed host's log (a post-publish "
                    f"best-copy/prune failure leaves it valid on disk)")
    return final


def _agree_phase_ok(error: Optional[BaseException], epoch: int,
                    phase: str, detail: str) -> None:
    """Agree a per-host phase outcome before anyone proceeds past it.

    The sharded layout's barriers have no timeout, so a host raising its
    local error while its peers enter the next collective would hang the
    job forever (round-4/5 advisor — this held for shard writes, tmp-dir
    prepare, and process 0's publish body alike). Every host calls this
    at the same logical step; afterwards all hosts either proceed
    together or raise together — peers of a failed host raise
    ``PeerFailure`` naming it, the failed host re-raises its own error.

    Since the supervision retrofit this delegates to
    ``runtime/supervision.py``: the agreement exchanges full supervision
    records (so a poison pill from a host that failed OUTSIDE a
    checkpoint phase is understood here and attributed to its real
    phase), runs under the configured watchdog deadline, and the
    allgather itself synchronizes, so callers may rely on this as a
    barrier.
    """
    from pytorch_distributed_mnist_tpu.runtime import supervision

    if jax.process_count() > 1:
        failed = supervision.agree(f"ckpt_{phase}", error)
        if failed and error is None:
            raise supervision.PeerFailure(
                supervision.peer_failure_message(
                    failed,
                    f"sharded checkpoint {phase} for epoch {epoch} failed "
                    f"on host(s) {[h for h, _, _ in failed]}; {detail}",
                ),
                hosts=[h for h, _, _ in failed],
                # The failed peer's OWN reported phase: a poison pill
                # from a host that died outside checkpointing must be
                # attributed to its real failure site, not to whichever
                # checkpoint agreement happened to receive the pill.
                phase=failed[0][1],
                reason=failed[0][2],
            )
    if error is not None:
        raise error


def _save_sharded(named, *, epoch: int, best_acc: float, is_best: bool,
                  directory: str, pid: int, keep_last: int = 0,
                  parallel_layout: Optional[Dict[str, Any]] = None) -> str:
    """Every process writes its owned shards; process 0 publishes the dir.

    Synchronous composition of the four phases; the AsyncCheckpointer
    runs phases 1-2 inline, phase 3 on its writer thread, and phase 4 at
    the next main-thread drain point."""
    tmp, final = _sharded_prepare(directory, epoch, pid)
    err: Optional[BaseException] = None
    try:
        # The WHOLE produce-this-host's-files phase is under the
        # agreement — a collect (device read) or meta failure outside it
        # would strand peers in the agreement collective just as a write
        # failure once stranded them in the publish barrier. Exception,
        # not BaseException: a KeyboardInterrupt on the main thread must
        # propagate immediately, not be held hostage by an allgather.
        os.makedirs(tmp, exist_ok=True)  # this host's view of the dir
        payload, index = _sharded_collect(named, pid)
        meta = (_sharded_meta(named, epoch, best_acc, parallel_layout)
                if pid == 0 else None)
        _sharded_write_files(tmp, pid, payload, index, meta)
    except Exception as exc:
        err = exc
    _agree_phase_ok(err, epoch, "write", f"dropping unpublished {tmp}")
    return _sharded_publish(tmp, final, directory, epoch, is_best,
                            keep_last, pid)


def _load_sharded(path: str, state) -> Tuple[Any, int, float]:
    """Stitch global arrays from the shard index, redistribute to ``state``.

    World-agnostic by construction, and that generality is load-bearing
    (the elastic runtime's reshard-resume path, ``runtime/elastic.py``):
    the shard index is keyed by global slice regions, not by the saving
    world's topology, so the loader reads WHATEVER set of per-process
    index files the directory holds, assembles each full global array
    on the host, and hands it to ``_restore_onto_template`` to place
    with the template leaf's sharding. A state saved from a ``(4, 2)``
    mesh of 4 processes restores onto an ``(8,)`` mesh, a single
    device, or a 3-process shrunk world unchanged — the loading world's
    process count and mesh never have to match the saving world's.

    The saving world's shape (``meta["world"]``, when stamped) is used
    only for diagnostics: a shard-coverage gap is reported as the
    incomplete filesystem view it is, naming how many index files the
    saving world wrote versus how many are visible here.
    """
    meta, globals_np = _stitch_sharded(path)
    new_state = _restore_onto_template(
        path, meta["leaf_names"], globals_np, state
    )
    return new_state, int(meta["epoch"]), float(meta["best_acc"])


def _stitch_sharded(path: str) -> Tuple[Dict[str, Any], list]:
    """The sharded layout's host-side stitch: ``(meta, global arrays)``
    assembled from the per-process shard index — shared by the restore
    path and by ``read_checkpoint_arrays`` (the delta publish converter
    reads a ``.ckpt`` dir through this, so a multi-host sharded save
    can be republished as a manifest without a template state)."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    n_leaves = len(meta["leaf_names"])
    globals_np = [
        np.zeros(shape, dtype=np.dtype(dt))
        for shape, dt in zip(meta["global_shapes"], meta["dtypes"])
    ]
    filled = [0] * n_leaves
    index_files = 0
    for idx_name in sorted(os.listdir(path)):
        if not idx_name.startswith("index_p"):
            continue
        index_files += 1
        with open(os.path.join(path, idx_name)) as f:
            idx = json.load(f)
        if idx["file"] is None:
            continue
        shard_path = os.path.join(path, idx["file"])
        if not os.path.isfile(shard_path):
            continue  # the filled-element check below reports what's missing
        with np.load(shard_path) as z:
            for rec in idx["shards"]:
                i = rec["leaf"]
                region = tuple(
                    slice(a, b) for a, b in zip(rec["start"], rec["stop"])
                )
                data = z[rec["key"]]
                globals_np[i][region] = data.reshape(globals_np[i][region].shape)
                filled[i] += data.size
    saved_procs = (meta.get("world") or {}).get("processes")
    for i, (total, arr) in enumerate(zip(filled, globals_np)):
        if total < arr.size:
            world = (f" (saved by a {saved_procs}-process world; "
                     f"{index_files} index file(s) visible here — an "
                     f"incomplete shared-filesystem view?)"
                     if saved_procs and index_files != saved_procs else
                     " — incomplete save?")
            raise ValueError(
                f"{path}: leaf {meta['leaf_names'][i]} is missing shards "
                f"({total}/{arr.size} elements present){world}"
            )
    return meta, globals_np


def _restore_onto_template(path, leaf_names, arrays, state):
    """Map saved host arrays onto the template state's leaves/shardings.

    Shared by both layouts: shape/count validation, dtype restore, and
    placement — ``device_put`` locally, ``make_array_from_callback`` when
    the template leaf spans non-addressable devices (each host supplies
    its own shards from the full host copy; no cross-host transfers).
    """
    flat, treedef = jax.tree_util.tree_flatten(_state_tree(state))
    if len(flat) != len(arrays):
        raise ValueError(
            f"{path}: checkpoint has {len(arrays)} leaves, current state "
            f"has {len(flat)} — model/optimizer mismatch"
        )
    restored = []
    for i, (tmpl, arr) in enumerate(zip(flat, arrays)):
        if tuple(np.shape(tmpl)) != arr.shape:
            raise ValueError(
                f"{path}: leaf {leaf_names[i]} shape {arr.shape} != "
                f"expected {tuple(np.shape(tmpl))}"
            )
        if hasattr(tmpl, "dtype"):
            arr = arr.astype(tmpl.dtype)
        sharding = getattr(tmpl, "sharding", None)
        if sharding is not None and not getattr(
            tmpl, "is_fully_addressable", True
        ):
            restored.append(jax.make_array_from_callback(
                arr.shape, sharding, lambda region, a=arr: a[region]
            ))
        elif sharding is not None:
            restored.append(jax.device_put(arr, sharding))
        else:
            restored.append(arr)
    return state.replace(**jax.tree_util.tree_unflatten(treedef, restored))


def load_checkpoint(path: str, state) -> Tuple[Any, int, float]:
    """Restore ``(state, start_epoch, best_acc)`` from ``path`` onto ``state``'s shardings.

    ``state`` is the freshly-constructed template (model + optimizer built
    exactly as at save time — the ``load_state_dict`` contract, ``:209-210``).
    Each saved leaf is ``device_put`` with the template leaf's sharding:
    restore-time resharding across mesh shapes. Directory paths are the
    sharded layout; ``.manifest`` files are the content-addressed delta
    layout (assembled from the adjacent chunk store — so resume and
    serve boot read a delta-published run with no extra code path);
    other files are the npz layout.
    """
    if os.path.isdir(path):
        return _load_sharded(path, state)
    if path.endswith(".manifest"):
        from pytorch_distributed_mnist_tpu.distrib.cas import (
            load_manifest_arrays,
        )

        manifest, arrays = load_manifest_arrays(path)
        new_state = _restore_onto_template(
            path, manifest["leaf_names"], arrays, state)
        return new_state, int(manifest["epoch"]), float(manifest["best_acc"])
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        saved = [z[f"leaf_{i}"] for i in range(len(meta["leaf_names"]))]
    new_state = _restore_onto_template(path, meta["leaf_names"], saved, state)
    return new_state, int(meta["epoch"]), float(meta["best_acc"])


def read_checkpoint_arrays(path: str) -> Tuple[Dict[str, Any], list]:
    """``(meta, host arrays in leaf_names order)`` for ANY layout — npz
    file, sharded ``.ckpt`` dir (stitched), or manifest (assembled) —
    with no template state: the byte-level read the delta publish
    converter (``distrib/publish.py::publish_from_checkpoint``) and the
    round-trip tests build on."""
    if os.path.isdir(path):
        return _stitch_sharded(path)
    if path.endswith(".manifest"):
        from pytorch_distributed_mnist_tpu.distrib.cas import (
            load_manifest_arrays,
        )

        return load_manifest_arrays(path)
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        return meta, [z[f"leaf_{i}"]
                      for i in range(len(meta["leaf_names"]))]


def _read_meta(path: str) -> Dict[str, Any]:
    """The checkpoint's meta dict, without touching array bytes — the
    one dir-vs-npz container read behind every inspection gate
    (``checkpoint_parallel_layout``, ``checkpoint_world``), so a meta
    container change lands once."""
    if os.path.isdir(path):
        with open(os.path.join(path, "meta.json")) as f:
            return json.load(f)
    if path.endswith(".manifest"):
        # The manifest IS meta (plus chunk refs): same epoch/world/
        # parallel_layout keys, so every inspection gate reads it as-is.
        with open(path) as f:
            return json.load(f)
    with np.load(path) as z:
        return json.loads(bytes(z["__meta__"]).decode())


def checkpoint_parallel_layout(path: str) -> Optional[Dict[str, Any]]:
    """Read just the ``parallel_layout`` provenance stamp from a
    checkpoint's meta — no array bytes touched, so the serve boot/reload
    layout gate can run before (and far cheaper than) the template load.
    Returns ``None`` for checkpoints saved without the stamp (library
    callers, pre-stamp files): no provenance, nothing to contradict."""
    layout = _read_meta(path).get("parallel_layout")
    return dict(layout) if layout is not None else None


def checkpoint_world(path: str) -> Optional[Dict[str, int]]:
    """Read just the saving world's shape (``{"processes": P,
    "devices": D}``) from a checkpoint's meta — no array bytes touched.

    The inspection twin of ``checkpoint_parallel_layout``: the elastic
    resume path and serve boot read it to KNOW a restore is a
    cross-world reshard (and log/record it) instead of discovering
    world provenance from a failed load. Returns ``None`` for
    checkpoints saved before the stamp existed — no provenance, and the
    restore path reshards regardless."""
    world = _read_meta(path).get("world")
    return ({"processes": int(world["processes"]),
             "devices": int(world["devices"])}
            if world is not None else None)


def is_corrupt_checkpoint_error(exc: BaseException) -> bool:
    """True when a ``load_checkpoint`` failure means the FILE is damaged
    (truncated download, torn write, lost shard file) rather than the
    CALLER being wrong (model/optimizer mismatch -> shape/leaf-count
    ValueErrors, path typo on a fresh run).

    The distinction gates resume-time quarantine: a corrupt latest
    checkpoint is renamed ``*.corrupt`` and resume falls back to the
    next-older epoch, while a mismatch must keep aborting loudly —
    quarantining a perfectly good checkpoint because the user changed
    ``--model`` would silently destroy their training history.

    Only CONTENT-level damage qualifies (bytes present but undecodable).
    Absence-level signals — a published ``.ckpt`` directory "missing"
    meta.json or a shard file — are NOT corruption: the atomic publish
    means a published directory was complete when renamed, so a missing
    member at resume time is far more likely a stale NFS attribute/
    readdir cache serving an incomplete view, and quarantining on it
    would destroy the newest good checkpoint. Those abort loudly.
    """
    import zipfile
    import zlib

    if isinstance(exc, (zipfile.BadZipFile, zlib.error, EOFError,
                        json.JSONDecodeError)):
        return True
    if isinstance(exc, KeyError):
        # npz member missing (__meta__/leaf_N): a torn or foreign zip
        # (zip content, not filesystem absence — the file itself decoded).
        return True
    if isinstance(exc, ValueError):
        # np.load on a non-zip is corruption; shape/leaf-count
        # mismatches (and _load_sharded's missing-shards complaint,
        # which is absence-level) are not.
        msg = str(exc)
        return ("Cannot load file" in msg
                or "Failed to interpret" in msg or "allow_pickle" in msg)
    return False


def quarantine_checkpoint(path: str) -> str:
    """Rename a corrupt checkpoint out of the resolution namespace.

    ``checkpoint_{e}.npz`` -> ``checkpoint_{e}.npz.corrupt`` (numbered
    ``.corrupt2``... if a previous quarantine of the same epoch exists),
    for both layouts — ``_epoch_checkpoints``'s pattern cannot match the
    suffix, so ``latest_checkpoint`` falls back to the next-older epoch
    and pruning never touches the evidence. Returns the quarantine path.
    """
    dest = path + CORRUPT_SUFFIX
    n = 2
    while os.path.exists(dest):
        dest = f"{path}{CORRUPT_SUFFIX}{n}"
        n += 1
    os.replace(path, dest)
    return dest


def _epoch_checkpoints(directory: str) -> list:
    """All published per-epoch checkpoints in ``directory`` as sorted
    ``(epoch, path)`` pairs. The single source of the eligibility rule for
    both resume selection and pruning (so they can never disagree about
    what counts as a checkpoint). All three layouts match (``.npz`` file,
    ``.ckpt`` dir, ``.manifest`` delta publish — so manifests ride the
    same resolution, watcher polling, and prune window with no second
    rule); the atomic writers' in-flight ``.tmp`` names never do,
    so a crash mid-save can only ever expose the last *published* file —
    the restart-from-checkpoint recovery model SURVEY.md section 5
    prescribes."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"checkpoint_(\d+)\.(npz|ckpt|manifest)", name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(out)


def latest_checkpoint(directory: str) -> Optional[str]:
    """Path of the highest-epoch ``checkpoint_{e}`` in ``directory``, or None.

    Multi-host callers must agree on the result across processes (NFS
    attribute caches can show different listings); ``cli.run`` resolves on
    process 0 and broadcasts.
    """
    found = _epoch_checkpoints(directory)
    return found[-1][1] if found else None


def prune_checkpoints(directory: str, keep_last: int) -> None:
    """Delete per-epoch checkpoints strictly older than the latest
    *published* epoch minus ``keep_last``.

    The reference retains every epoch's file with no GC (``:267-268``) and
    so does this framework by default (``keep_last <= 0``); this is the
    opt-in bound for long runs. ``model_best`` copies are never pruned.
    Only process 0 calls this (same gate as the npz write).

    ORDERING GUARANTEE (the serve hot-reload contract,
    ``serve/reload.py``): pruning is keyed off the latest PUBLISHED epoch
    ``L`` and deletes only epochs ``e < L - keep_last`` — the window
    ``[L - keep_last, L]`` always survives. A reload watcher only ever
    starts loading the latest published checkpoint it can see, and
    pruning runs only as part of publishing a newer one, so with
    ``keep_last >= 1`` the checkpoint a watcher is mid-load on stays on
    disk for at least ``keep_last`` further publishes (one full epoch of
    training each) before it can be deleted — a load would have to
    straddle ``keep_last`` whole epochs to race the GC. A count-based
    "keep the N newest files" rule (the pre-serving behavior) has no such
    bound: publish + prune could delete the previous latest at the exact
    moment a watcher opened it.
    """
    if keep_last <= 0:
        return
    found = _epoch_checkpoints(directory)
    if not found:
        return
    latest_epoch = found[-1][0]
    for epoch, path in found:
        if epoch >= latest_epoch - keep_last:
            break  # sorted: everything from here on is inside the window
        if os.path.isdir(path):
            shutil.rmtree(path)
        else:
            os.remove(path)


class AsyncCheckpointer:
    """Overlap checkpoint file I/O with the next epoch's compute.

    ``save()`` snapshots every leaf (npz layout) or every OWNED shard
    (sharded layout) to host memory synchronously — the only part that
    must see a consistent device state; the train loop is free to
    donate/overwrite buffers the moment it returns — then runs the file
    writes on a single worker thread. ``wait()`` joins the in-flight
    write; it is called before the next ``save`` (one write in flight at
    most, so a slow disk can delay training by at most one checkpoint),
    at context exit, and returns the last written path.

    Sharded (multi-host) layout: the layout's correctness barriers are
    device collectives, and running those on a side thread while the
    main thread launches train steps could interleave two collective
    programs — a deadlock. So the phases split (Orbax-style commit):
    tmp-dir prepare (barrier) + shard snapshot run inline in ``save()``,
    the shard/index/meta file writes run on the writer thread, and the
    publish barrier + atomic rename run at the NEXT main-thread drain
    point (the next ``save()`` or the context exit). Every process
    drains at the same logical step, so the deferred collectives match.
    Net effect: epoch N's directory is published at epoch N+1's save —
    a crash loses at most the one unpublished write, the same guarantee
    the async npz path gives for its in-flight file.
    """

    def __init__(self) -> None:
        self._thread = None
        self._result: Optional[str] = None
        self._error: Optional[BaseException] = None
        self._pending_publish: Optional[Dict[str, Any]] = None

    def save(self, state, **kwargs) -> None:
        self.wait()
        # Arm fresh: from here on _result must only ever hold THIS save's
        # outcome. Without this, a failed write/publish leaves the
        # PREVIOUS epoch's path in _result, and a later wait() (e.g.
        # after the caller caught the error) would return that stale path
        # as if it were the latest save's (round-5 advisor).
        self._result = None
        named = _leaves_with_names(_state_tree(state))
        layout = kwargs.pop("layout", None)
        if layout not in (None, "npz", "sharded"):
            raise ValueError(f"unknown checkpoint layout {layout!r}")
        if kwargs.get("publish") == "delta":
            # The async delta path rides the npz machinery below: a
            # pid-0 host snapshot inline, chunking + manifest write on
            # the writer thread (``save_checkpoint`` routes on the
            # ``publish`` kwarg it keeps in ``kwargs``). Sharded states
            # must fail HERE — silently falling through to the sharded
            # layout would drop the requested delta publish.
            if layout == "sharded" or not all(
                _npz_saveable(v) for _, v in named
            ):
                raise ValueError(
                    "--publish delta requires fully-addressable (or "
                    "replicated) leaves; save the sharded layout and "
                    "convert via publish_from_checkpoint")
        elif layout == "sharded" or (
            layout is None and not all(_npz_saveable(v) for _, v in named)
        ):
            self._save_sharded_async(named, kwargs)
            return
        pid = kwargs.get("process_index")
        if (jax.process_index() if pid is None else pid) != 0:
            # npz saves are process-0-only; snapshotting a full host copy
            # of params+moments (and spawning a thread) on every other
            # host would buy nothing but RAM pressure.
            self._result = None
            return
        host_state = jax.tree.map(np.asarray, _state_tree(state))
        snapshot = _HostState(host_state)

        def _write() -> None:
            try:
                # Annotated on THIS thread's timeline: the main thread's
                # "checkpoint_drain" span only covers waiting for us.
                with jax.profiler.TraceAnnotation(
                    "checkpoint_async_write", epoch=kwargs.get("epoch", -1)
                ):
                    self._result = save_checkpoint(snapshot, **kwargs)
            except BaseException as exc:  # surfaced by the next wait()
                self._error = exc

        import threading

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def _save_sharded_async(self, named, kwargs: Dict[str, Any]) -> None:
        epoch = kwargs["epoch"]
        directory = kwargs.get("directory", CHECKPOINT_DIR)
        pid = kwargs.get("process_index")
        pid = jax.process_index() if pid is None else pid
        # Phases 1-2 inline: the tmp-clean barrier (collective) and the
        # owned-shard D2H snapshot (device reads).
        tmp, final = _sharded_prepare(directory, epoch, pid)
        # Phase 4 bookkeeping is armed EVEN when the inline snapshot
        # below fails: the next drain's write-ok agreement then fails
        # every host together, instead of this host raising alone while
        # its peers wait at that drain's collective forever (the same
        # strand class _agree_phase_ok closes for write failures).
        pending = dict(
            tmp=tmp, final=final, directory=directory, epoch=epoch,
            is_best=kwargs.get("is_best", False),
            keep_last=kwargs.get("keep_last", 0), pid=pid,
        )
        try:
            os.makedirs(tmp, exist_ok=True)  # this host's view of the dir
            payload, index = _sharded_collect(named, pid)
            meta = (_sharded_meta(named, epoch, kwargs["best_acc"],
                                  kwargs.get("parallel_layout"))
                    if pid == 0 else None)
        except Exception as exc:
            self._error = exc
            self._pending_publish = pending
            return

        def _write() -> None:
            try:
                with jax.profiler.TraceAnnotation(
                    "checkpoint_async_write", epoch=epoch
                ):
                    _sharded_write_files(tmp, pid, payload, index, meta)
            except BaseException as exc:  # surfaced by the next wait()
                self._error = exc

        # Phase 4 runs at the next drain, on the main thread.
        self._pending_publish = pending
        import threading

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> Optional[str]:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._pending_publish is not None:
            pub, self._pending_publish = self._pending_publish, None
            err, self._error = self._error, None
            # Every host drains at the same logical step, so the
            # agreement collective lines up; it raises (on every host)
            # when any host's write failed, leaving the tmp dir for
            # postmortem and the publish barrier unentered.
            _agree_phase_ok(err, pub["epoch"], "write",
                            f"dropping unpublished {pub['tmp']}")
            self._result = _sharded_publish(**pub)
        if self._error is not None:
            exc, self._error = self._error, None
            raise exc
        return self._result

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, *exc_info) -> None:
        # Swallow nothing: a failed in-flight write must fail the run,
        # unless the body is already unwinding on its own exception.
        if exc_info[0] is None:
            self.wait()
        else:
            from pytorch_distributed_mnist_tpu.runtime import supervision
            from pytorch_distributed_mnist_tpu.utils.profiling import (
                failure_events,
            )

            if self._thread is not None:
                self._thread.join()
                self._thread = None
            if self._error is not None:
                # The with-body is unwinding on its own exception, which
                # must not be masked — but a silently dropped write error
                # makes the lost checkpoint invisible to postmortems
                # (round-4 advisor). Say what failed before discarding.
                print(
                    "WARNING: async checkpoint write failed while the "
                    f"run was unwinding; the write error is discarded in "
                    f"favor of the run's own exception: {self._error!r}",
                    file=sys.stderr,
                )
                failure_events.record(
                    "async_write_error_discarded", repr(self._error))
                self._error = None
            if self._pending_publish is not None:
                # Never run the deferred publish barrier while unwinding:
                # a PEER failure (or watchdog abort) means the other
                # hosts are unwinding too and would never arrive. The
                # unpublished tmp dir is named so the epoch's loss is
                # visible, not silent.
                print(
                    "WARNING: unpublished checkpoint "
                    f"{self._pending_publish['tmp']} dropped during "
                    "unwind (publish barrier skipped)",
                    file=sys.stderr,
                )
                failure_events.record(
                    "pending_publish_dropped", self._pending_publish["tmp"])
                self._pending_publish = None
            # The agreed exit (ADVICE.md residual hazard, now closed):
            # a HOST-LOCAL failure must not let this host vanish while
            # its peers proceed to the next drain's write agreement and
            # block forever in it. Delivering the poison pill here —
            # inside the saver's scope boundary — covers every
            # AsyncCheckpointer user, not just cli.run (whose supervised
            # scope calls this too; delivery is idempotent per
            # exception, so the pill goes out exactly once).
            supervision.deliver_poison(exc_info[1])


class _HostState:
    """Duck-typed stand-in for a TrainState whose leaves are host arrays:
    exactly the attributes ``_state_tree`` reads, nothing else."""

    def __init__(self, tree: Dict[str, Any]) -> None:
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.step = tree["step"]
        self.buffers = tree.get("buffers")


def try_resume(path: str, state) -> Tuple[Any, int, float]:
    """Reference resume policy (``:197-214``): load if the file exists, else
    warn and continue fresh with ``(state, 0, 0.0)``.

    ``path == 'auto'`` resolves to the newest checkpoint in the run's
    checkpoint directory (see ``cli.py``) — the restart-after-preemption
    mode: the same command line works for the first launch (no checkpoint
    yet, trains fresh) and every relaunch (continues where it died).
    """
    if path and (os.path.isfile(path) or os.path.isdir(path)):
        state, start_epoch, best_acc = load_checkpoint(path, state)
        print(f"=> loaded checkpoint '{path}' (epoch {start_epoch})")
        return state, start_epoch, best_acc
    if path:
        print(f"=> no checkpoint found at '{path}'")
    return state, 0, 0.0
