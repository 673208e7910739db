"""Sharded checkpoint I/O on the shard-store core (repro/io, DESIGN.md §7).

Layout of a checkpoint directory:

  step_000100/
    MANIFEST.json              {step, leaf keys/shapes/dtypes/specs, treedef}
    leaves/leaf_00000/         one shard STORE per pytree leaf:
      MANIFEST.json              shard index -> global slice
      shards/shard_00000.bin     one file per addressable device shard
    .COMMITTED                 written last -> atomic visibility

Semantics:
  * Each host writes only the shards it owns (`array.addressable_shards`),
    mirroring the paper's slice-per-rank PFS store — the global array is
    never gathered to one host.
  * Restore is *mesh-agnostic*: the manifest stores the logical
    PartitionSpec (None when the saved leaf recorded no spec — a host array
    or default placement; an empty list is a real, fully-replicated spec),
    and `load_checkpoint` scatter-reads each leaf onto whatever mesh the
    restarted job has, opening only the shard files its target regions
    intersect — the elastic-scaling path (512 -> 448 chips) is the same
    code path as a plain restart.
  * Corruption fails loudly: a truncated shard file, a missing manifest
    entry and a missing `.COMMITTED` marker each raise `StoreError` naming
    the offending path, and `CheckpointManager.restore_latest` falls back
    to the newest step that does load.
  * `CheckpointManager` runs saves on a background thread (async
    checkpointing, via per-shard host snapshots — `shard_store.snapshot`),
    keeps the newest K checkpoints, never deletes the last committed one,
    and sweeps `step_*.tmp` directories orphaned by a crashed writer.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.io import shard_store
from repro.io.shard_store import StoreError

PyTree = Any

_STEP_RE = re.compile(r"^step_(\d+)$")
_TMP_RE = re.compile(r"^step_(\d+)\.tmp$")


def _spec_from_json(spec) -> PartitionSpec:
    parts = []
    for e in spec:
        if isinstance(e, list):
            parts.append(tuple(e))
        else:
            parts.append(e)
    return PartitionSpec(*parts)


def _leaf_spec(leaf) -> Optional[list]:
    """JSON PartitionSpec of a leaf, or None when none is recorded. The
    None/[] distinction is real: [] is PartitionSpec() (fully replicated,
    re-apply it on restore), None means the saved leaf had no spec at all
    (host array / default placement — restore with default placement)."""
    if isinstance(leaf, shard_store.HostShardedArray):
        return leaf.spec
    return shard_store.leaf_spec_json(leaf)


def _sweep_orphaned_tmp(directory: str) -> List[str]:
    """Remove `step_*.tmp` directories a crashed writer left behind. They
    must neither accumulate nor shadow a later save of the same step (a
    stale tmp would leak its leaf files into the renamed checkpoint)."""
    removed = []
    if not os.path.isdir(directory):
        return removed
    for name in os.listdir(directory):
        if _TMP_RE.match(name):
            shutil.rmtree(os.path.join(directory, name), ignore_errors=True)
            removed.append(name)
    return removed


def save_checkpoint(directory: str, step: int, tree: PyTree) -> str:
    """Write a committed checkpoint for `tree` at `step`. Returns its path.

    Leaves may be jax Arrays (each host writes its addressable shards),
    host numpy values, or `shard_store.HostShardedArray` snapshots (the
    async manager path).
    """
    path = os.path.join(directory, f"step_{step:08d}")
    tmp = path + ".tmp"
    if os.path.exists(tmp):  # stale writer: do not inherit its files
        shutil.rmtree(tmp)
    leaves_dir = os.path.join(tmp, "leaves")
    os.makedirs(leaves_dir, exist_ok=True)
    flat, treedef = jax.tree.flatten_with_path(tree)
    manifest = {"step": step, "format": "shard-store-v1", "leaves": []}
    for idx, (keypath, leaf) in enumerate(flat):
        name = f"leaf_{idx:05d}"
        shard_store.save_array(os.path.join(leaves_dir, name), leaf)
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        manifest["leaves"].append(
            {
                "name": name,
                "key": jax.tree_util.keystr(keypath),
                "shape": list(shape),
                "dtype": str(np.dtype(dtype)),
                "spec": _leaf_spec(leaf),
            }
        )
    manifest["treedef"] = str(treedef)
    with open(os.path.join(tmp, "MANIFEST.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    open(os.path.join(tmp, ".COMMITTED"), "w").close()
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)
    return path


def committed_steps(directory: str) -> List[int]:
    """All committed step numbers, ascending."""
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        m = _STEP_RE.match(name)
        if m and os.path.exists(os.path.join(directory, name, ".COMMITTED")):
            steps.append(int(m.group(1)))
    return sorted(steps)


def latest_step(directory: str) -> Optional[int]:
    steps = committed_steps(directory)
    return steps[-1] if steps else None


def load_checkpoint(directory: str, step: int, like: PyTree,
                    mesh: Optional[Mesh] = None) -> PyTree:
    """Restore into the structure of `like`, re-sharded for `mesh`.

    `like` provides the pytree structure (e.g. from `jax.eval_shape` of the
    init fn); the manifest's PartitionSpecs are re-applied on `mesh`, which
    may differ in shape from the mesh that wrote the checkpoint (elastic
    restart) — each leaf is scatter-read: only the shard files overlapping
    this host's target regions are opened.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    mpath = os.path.join(path, "MANIFEST.json")
    if not os.path.exists(mpath):
        raise StoreError(f"no checkpoint manifest at {mpath!r}")
    if not os.path.exists(os.path.join(path, ".COMMITTED")):
        raise StoreError(
            f"checkpoint {path!r} is uncommitted (no .COMMITTED marker): "
            "the writer crashed mid-save; restore an earlier step")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        raise StoreError(f"unreadable checkpoint manifest {mpath!r}: {e}"
                         ) from e
    flat, treedef = jax.tree.flatten(like)
    if len(flat) != len(manifest["leaves"]):
        raise ValueError(
            f"checkpoint has {len(manifest['leaves'])} leaves, expected {len(flat)}"
        )
    out = []
    for leaf_like, meta in zip(flat, manifest["leaves"]):
        leaf_dir = os.path.join(path, "leaves", meta["name"])
        if list(meta["shape"]) != list(np.shape(leaf_like)):
            raise ValueError(
                f"{meta['key']}: checkpoint shape {tuple(meta['shape'])} != "
                f"expected {np.shape(leaf_like)}"
            )
        if mesh is not None and meta["spec"] is not None:
            sharding = NamedSharding(mesh, _spec_from_json(meta["spec"]))
            out.append(shard_store.load_array(leaf_dir, sharding))
        else:
            out.append(jax.device_put(shard_store.load_array(leaf_dir)))
    return jax.tree.unflatten(treedef, out)


class CheckpointManager:
    """Async checkpointing with retention + orphan sweep (DESIGN.md §7)."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)
        _sweep_orphaned_tmp(directory)  # crashed-writer leftovers

    def save(self, step: int, tree: PyTree, blocking: bool = False) -> None:
        # Snapshot shard-by-shard to host memory synchronously (cheap, and
        # keeps each shard's global index + the leaf's PartitionSpec for
        # the per-shard files), write async.
        host_tree = jax.tree.map(shard_store.snapshot, tree)
        self.wait()

        def _write():
            save_checkpoint(self.directory, step, host_tree)
            self._gc()

        if blocking:
            _write()
        else:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore_latest(self, like: PyTree, mesh: Optional[Mesh] = None):
        """(step, tree) from the newest loadable committed checkpoint.

        A corrupted newest step (truncated shard, gutted manifest — any
        StoreError) is skipped with the next-newest tried instead, so one
        bad write never strands a restart; (None, None) when nothing
        committed loads.
        """
        self.wait()
        last_err: Optional[StoreError] = None
        for step in reversed(committed_steps(self.directory)):
            try:
                return step, load_checkpoint(self.directory, step, like, mesh)
            except StoreError as e:
                last_err = e
                continue
        if last_err is not None:
            import warnings

            warnings.warn(f"no committed checkpoint loads cleanly; last "
                          f"error: {last_err}", RuntimeWarning)
        return None, None

    def _gc(self) -> None:
        _sweep_orphaned_tmp(self.directory)
        steps = committed_steps(self.directory)
        for s in steps[: -self.keep] if len(steps) > self.keep else []:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{s:08d}"), ignore_errors=True
            )
