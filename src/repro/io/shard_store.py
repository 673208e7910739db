"""Shard-level array store: one file per addressable shard + a manifest.

The paper's end-to-end numbers ("4K within 30 s *including I/O*") rest on a
slice-per-rank parallel-filesystem store: every rank streams its own slab to
its own file, so aggregate bandwidth scales with the rank count instead of
funnelling through one writer. This module is that store for arbitrary JAX
arrays (DESIGN.md §7):

  <dir>/
    MANIFEST.json            {shape, dtype, spec, shards: [...]}
    shards/shard_00000.bin   raw little-endian C-order bytes, one file per
    shards/shard_00001.bin   distinct device shard (replicas deduplicated)
    ...

Write side — `save_array`: each host writes only the shards it owns
(`array.addressable_shards`, `replica_id == 0` copies), never materializing
the global array; shard file names are derived from the *global* index map
so every host agrees on the layout without coordination, and process 0
writes the manifest.

Read side — `load_array(path, sharding=...)`: a scatter read. For every
distinct region the target sharding places on this host's devices, only the
shard files that intersect that region are opened (memory-mapped, so a
region that needs one row of a shard reads ~one row, not the file); the
pieces are assembled per device and joined with
`jax.make_array_from_single_device_arrays`. Restoring onto a different mesh
shape than the writer's (the elastic 8 -> 4 path) is the same code path —
the store is indexed by global coordinates, not by writer rank.

Shard files are raw bytes (not .npy) for two reasons: numpy's format cannot
represent the ml_dtypes storage types (bfloat16 projections), and a raw
file's expected size is exactly `prod(extent) * itemsize` — truncation by a
crashed or out-of-quota writer is detected by a size check before any data
is trusted. All corruption paths raise `StoreError` with the offending
path; `open_count()` exposes file-open accounting so tests (and the `io`
benchmark suite) can assert scatter reads touch only what they need.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

Index = Tuple[Tuple[int, int], ...]     # ((lo, hi), ...) per dimension

MANIFEST = "MANIFEST.json"
SHARD_DIR = "shards"


class StoreError(RuntimeError):
    """A shard store (or checkpoint built on it) is unreadable: truncated
    shard file, missing manifest / manifest entry, or an uncommitted step."""


# ---------------------------------------------------------------------------
# file-open accounting (scatter-read tests, io benchmark suite)

_OPEN_COUNT = 0


def reset_open_count() -> None:
    global _OPEN_COUNT
    _OPEN_COUNT = 0


def open_count() -> int:
    """Shard files opened since `reset_open_count()` (reads only)."""
    return _OPEN_COUNT


# ---------------------------------------------------------------------------
# dtypes / indices

def dtype_from_name(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bfloat16/float8 storage dtypes (jax dependency)

        try:
            return np.dtype(getattr(ml_dtypes, name))
        except AttributeError:
            raise StoreError(f"manifest names unknown dtype {name!r}")


def _normalize_index(index: Sequence[slice], shape: Sequence[int]) -> Index:
    """Tuple-of-slices (as produced by shard.index / devices_indices_map,
    possibly with None bounds) -> ((lo, hi), ...) in global coordinates."""
    out = []
    for sl, dim in zip(index, shape):
        lo, hi, step = sl.indices(dim)
        if step != 1:
            raise StoreError(f"non-unit-stride shard index {sl} unsupported")
        out.append((lo, hi))
    return tuple(out)


def _extent(index: Index) -> Tuple[int, ...]:
    return tuple(hi - lo for lo, hi in index)


def _size(index: Index) -> int:
    n = 1
    for lo, hi in index:
        n *= hi - lo
    return n


def _intersect(a: Index, b: Index) -> Optional[Index]:
    out = []
    for (alo, ahi), (blo, bhi) in zip(a, b):
        lo, hi = max(alo, blo), min(ahi, bhi)
        if lo >= hi:
            return None
        out.append((lo, hi))
    return tuple(out)


def _rel_slices(outer: Index, inner: Index) -> Tuple[slice, ...]:
    """`inner` (global coords) as slices into an array spanning `outer`."""
    return tuple(slice(ilo - olo, ihi - olo)
                 for (olo, _), (ilo, ihi) in zip(outer, inner))


# ---------------------------------------------------------------------------
# host-side snapshot (async checkpointing keeps shard structure, not a
# gathered global array)

@dataclasses.dataclass
class HostShardedArray:
    """A device array snapshotted to host memory shard-by-shard: what the
    CheckpointManager's background writer consumes. Keeps the global shape,
    the logical PartitionSpec (JSON form, None = no spec recorded), the
    GLOBAL shard index table (so a multi-host writer numbers its files
    consistently with every other host and the manifest lists shards this
    host does not own), and one (index, data) pair per owned shard — never
    the assembled array."""

    shape: Tuple[int, ...]
    dtype: Any
    spec: Optional[list]
    shards: list            # [(Index, np.ndarray)] — owned by this host
    table: Optional[list] = None  # [Index] global, sorted; None = shards


def leaf_spec_json(arr) -> Optional[list]:
    """The logical PartitionSpec of `arr` in JSON form, or None when the
    array records no spec (host numpy, single-device default placement).
    None-vs-list is load-bearing: an empty list is a *real* (fully
    replicated) PartitionSpec, not the absence of one."""
    from jax.sharding import NamedSharding

    sharding = getattr(arr, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    out: list = []
    for e in sharding.spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append(list(e))
        else:
            out.append(e)
    return out


def snapshot(leaf) -> Any:
    """Device array -> HostShardedArray (per-shard device_get, no global
    gather); host values pass through as numpy arrays."""
    if not isinstance(leaf, jax.Array):
        return np.asarray(leaf)
    shape = tuple(leaf.shape)
    shards = [
        (_normalize_index(s.index, shape), np.asarray(jax.device_get(s.data)))
        for s in leaf.addressable_shards
        if s.replica_id == 0
    ]
    return HostShardedArray(shape=shape, dtype=leaf.dtype,
                            spec=leaf_spec_json(leaf), shards=shards,
                            table=_global_shard_table(leaf))


# ---------------------------------------------------------------------------
# write side

def _chunk_indices(shape: Tuple[int, ...],
                   chunks: Sequence[int]) -> list[Index]:
    """Regular grid of `chunks[d]` pieces along each dim (host-array writes:
    a preprocessing job laying out slice-per-rank files without a mesh)."""
    if len(chunks) != len(shape):
        raise ValueError(f"chunks {tuple(chunks)} must have one entry per "
                         f"dimension of shape {shape}")
    per_dim = []
    for dim, n in zip(shape, chunks):
        if n < 1 or dim % n:
            raise ValueError(
                f"chunks {tuple(chunks)} must positively divide {shape}")
        step = dim // n
        per_dim.append([(i * step, (i + 1) * step) for i in range(n)])
    out: list[Index] = [()]
    for bounds in per_dim:
        out = [idx + (b,) for idx in out for b in bounds]
    return out


def _global_shard_table(arr: jax.Array) -> list[Index]:
    """Sorted distinct global shard indices — identical on every host, so
    shard file names need no coordination."""
    imap = arr.sharding.devices_indices_map(tuple(arr.shape))
    distinct = {_normalize_index(idx, arr.shape) for idx in imap.values()}
    return sorted(distinct)


def save_array(path: str, arr, *, chunks: Optional[Sequence[int]] = None,
               extra_manifest: Optional[dict] = None,
               _process_index: Optional[int] = None) -> str:
    """Write `arr` as a shard store at `path` (clearing any stale store).

    jax.Array        one file per distinct device shard; this host writes
                     only the shards it owns (replica 0 copies).
    HostShardedArray the snapshot path (async checkpoint writer).
    host array       one file, or a `chunks=(c0, c1, ...)` regular grid.

    `extra_manifest` merges additional keys into MANIFEST.json (reserved
    keys shape/dtype/spec/shards win) — e.g. the stream layer records the
    codec an encoded projection store was quantized with, so readers know
    to load the scale sidecar next to the data (repro/io/streams.py).
    """
    pidx = jax.process_index() if _process_index is None else _process_index
    if pidx == 0 and os.path.exists(path):
        # Only one process clears a stale store: a per-host rmtree would
        # race the other hosts' concurrent shard writes on a shared PFS.
        # (Best-effort without a barrier — stale shard files left by other
        # layouts are inert, reads go through the fresh manifest.)
        shutil.rmtree(path)
    shard_dir = os.path.join(path, SHARD_DIR)
    os.makedirs(shard_dir, exist_ok=True)

    if isinstance(arr, HostShardedArray):
        shape, dtype, spec = arr.shape, np.dtype(arr.dtype), arr.spec
        table = (sorted(tuple(tuple(b) for b in i) for i in arr.table)
                 if arr.table is not None
                 else sorted(idx for idx, _ in arr.shards))
        owned = dict(arr.shards)
    elif isinstance(arr, jax.Array) and chunks is None:
        shape, dtype = tuple(arr.shape), np.dtype(arr.dtype)
        spec = leaf_spec_json(arr)
        table = _global_shard_table(arr)
        owned = {
            _normalize_index(s.index, shape):
                np.asarray(jax.device_get(s.data))
            for s in arr.addressable_shards if s.replica_id == 0
        }
    else:
        data = np.asarray(jax.device_get(arr))
        shape, dtype, spec = tuple(data.shape), data.dtype, None
        table = (_chunk_indices(shape, chunks) if chunks is not None
                 else [tuple((0, d) for d in shape)])
        owned = {idx: data[tuple(slice(lo, hi) for lo, hi in idx)]
                 for idx in table}

    entries = []
    for i, idx in enumerate(table):
        fname = f"shard_{i:05d}.bin"
        entries.append({"file": fname, "index": [list(b) for b in idx]})
        if idx in owned:
            piece = np.ascontiguousarray(owned[idx])
            with open(os.path.join(shard_dir, fname), "wb") as f:
                f.write(piece.tobytes())
    if pidx == 0:
        manifest = dict(extra_manifest or {})
        manifest.update({
            "shape": list(shape),
            "dtype": str(dtype),
            "spec": spec,
            "shards": entries,
        })
        with open(os.path.join(path, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=1)
    return path


# ---------------------------------------------------------------------------
# streaming append (growing store: the scanner writes while readers poll)

def _write_manifest(path: str, manifest: dict) -> None:
    """Atomic manifest replace: readers polling a growing store either see
    the old manifest or the new one, never a torn write."""
    mpath = os.path.join(path, MANIFEST)
    tmp = mpath + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, mpath)


def init_store(path: str, shape: Sequence[int], dtype,
               extra_manifest: Optional[dict] = None) -> str:
    """Create an EMPTY shard store of a known final shape — the head of a
    streaming write (`append_region`): the manifest declares the full array
    up front with no shards, and grows one entry per committed append.
    Readers (`read_region` / a poller diffing `manifest["shards"]`) see only
    committed data."""
    os.makedirs(os.path.join(path, SHARD_DIR), exist_ok=True)
    manifest = dict(extra_manifest or {})
    manifest.update({
        "shape": list(shape),
        "dtype": str(np.dtype(dtype)),
        "spec": None,
        "shards": [],
    })
    _write_manifest(path, manifest)
    return path


def append_region(path: str, index: Sequence, data) -> dict:
    """Append one region to a growing store and COMMIT it.

    Write protocol (PFS-safe ordering): the shard file lands fully on disk
    first, then the manifest is atomically replaced with the new entry
    appended — the manifest entry is the commit point, so a reader never
    sees an entry whose bytes are not durable, and a crashed writer leaves
    at worst an orphaned (inert) shard file. Returns the new entry."""
    m = read_manifest(path)
    shape = tuple(m["shape"])
    idx = (tuple(tuple(b) for b in index) if not isinstance(index[0], slice)
           else _normalize_index(index, shape))
    dtype = dtype_from_name(m["dtype"])
    piece = np.ascontiguousarray(np.asarray(data, dtype=dtype))
    if piece.shape != _extent(idx):
        raise ValueError(
            f"append data shape {piece.shape} does not span index {idx}")
    for entry in m["shards"]:
        prev = tuple(tuple(b) for b in entry["index"])
        if _intersect(idx, prev) is not None:
            raise StoreError(
                f"append region {idx} overlaps committed shard "
                f"{entry['file']} ({prev}) in {path!r}")
    fname = f"shard_{len(m['shards']):05d}.bin"
    with open(os.path.join(path, SHARD_DIR, fname), "wb") as f:
        f.write(piece.tobytes())
        f.flush()
        os.fsync(f.fileno())
    entry = {"file": fname, "index": [list(b) for b in idx]}
    m["shards"].append(entry)
    _write_manifest(path, m)
    return entry


# ---------------------------------------------------------------------------
# read side

def read_manifest(path: str) -> dict:
    mpath = os.path.join(path, MANIFEST)
    if not os.path.exists(mpath):
        raise StoreError(f"no shard store at {path!r} (missing {MANIFEST})")
    try:
        with open(mpath) as f:
            return json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        raise StoreError(f"unreadable manifest {mpath!r}: {e}") from e


def _open_shard(path: str, entry: dict, dtype: np.dtype) -> np.ndarray:
    """Memory-map one shard file, verifying its size first (truncation from
    a crashed/out-of-quota writer must fail loudly, not read garbage)."""
    global _OPEN_COUNT
    idx = tuple(tuple(b) for b in entry["index"])
    extent = _extent(idx)
    fpath = os.path.join(path, SHARD_DIR, entry["file"])
    if not os.path.exists(fpath):
        raise StoreError(f"missing shard file {fpath!r}")
    expected = _size(idx) * dtype.itemsize
    actual = os.path.getsize(fpath)
    if actual != expected:
        raise StoreError(
            f"truncated shard file {fpath!r}: {actual} bytes on disk, "
            f"expected {expected} ({extent} x {dtype})")
    _OPEN_COUNT += 1
    if _size(idx) == 0 or extent == ():
        data = np.fromfile(fpath, dtype=dtype)
        return data.reshape(extent)
    return np.memmap(fpath, dtype=dtype, mode="r", shape=extent, order="C")


def read_region(path: str, index: Sequence[slice] | Index,
                manifest: Optional[dict] = None) -> np.ndarray:
    """Assemble one global-coordinate region, opening only the shard files
    that intersect it. Raises StoreError when the manifest's shards do not
    cover the region (a deleted/missing manifest entry)."""
    m = manifest if manifest is not None else read_manifest(path)
    shape = tuple(m["shape"])
    dtype = dtype_from_name(m["dtype"])
    if index and isinstance(index[0], slice):
        region = _normalize_index(index, shape)
    else:
        region = tuple(tuple(b) for b in index)
    out = np.empty(_extent(region), dtype=dtype)
    covered = 0
    for entry in m["shards"]:
        sidx = tuple(tuple(b) for b in entry["index"])
        inter = _intersect(region, sidx)  # () for 0-d: the shard covers it
        if inter is None:
            continue
        data = _open_shard(path, entry, dtype)
        out[_rel_slices(region, inter)] = data[_rel_slices(sidx, inter)]
        covered += _size(inter)
        if covered == _size(region):
            break
    if covered != _size(region):
        raise StoreError(
            f"shard store {path!r} does not cover region {region}: "
            f"{covered}/{_size(region)} elements present — missing or "
            "deleted manifest entries")
    return out


def read_shards(path: str, sharding) -> HostShardedArray:
    """The regions `sharding` places on this host's devices, read from the
    store into host memory: each distinct region once (replicas share it),
    opening only the shard files that intersect it. The host half of a
    scatter read; `put_shards` is the device half."""
    m = read_manifest(path)
    shape = tuple(m["shape"])
    regions: dict = {}
    for idx in sharding.addressable_devices_indices_map(shape).values():
        key = _normalize_index(idx, shape) if idx else ()
        if key not in regions:
            regions[key] = np.ascontiguousarray(
                read_region(path, key, manifest=m))
    return HostShardedArray(shape=shape, dtype=dtype_from_name(m["dtype"]),
                            spec=None, shards=list(regions.items()))


def put_shards(host: HostShardedArray, sharding) -> jax.Array:
    """`device_put` each device's region of `host` (from `read_shards`
    with the same `sharding`) and join the pieces into the global array."""
    regions = dict(host.shards)
    pieces = [
        jax.device_put(
            regions[_normalize_index(idx, host.shape) if idx else ()], dev)
        for dev, idx in
        sharding.addressable_devices_indices_map(host.shape).items()]
    return jax.make_array_from_single_device_arrays(host.shape, sharding,
                                                    pieces)


def load_array(path: str, sharding=None) -> Any:
    """Restore a stored array.

    sharding=None         assemble the full array on host (numpy).
    sharding=NamedSharding scatter read: for each distinct region the target
                          sharding places on this host, open only the
                          intersecting shard files and build the global
                          jax.Array — the target mesh need not match the
                          writer's (reshard-on-restore). Every region is
                          read before any piece goes to its device.
    """
    if sharding is None:
        m = read_manifest(path)
        return read_region(path, tuple((0, d) for d in m["shape"]),
                           manifest=m)
    return put_shards(read_shards(path, sharding), sharding)


def stored_spec(path: str):
    """The writer's logical PartitionSpec (or None if none was recorded)."""
    from jax.sharding import PartitionSpec

    spec = read_manifest(path).get("spec")
    if spec is None:
        return None
    return PartitionSpec(*[tuple(e) if isinstance(e, list) else e
                           for e in spec])
