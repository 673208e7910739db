"""Projection/volume endpoints of the reconstruction pipeline (paper Fig. 3).

The paper's rank does not receive projections from the caller — it *loads*
its N_p/(R*C) slice from the parallel filesystem, and it does not return its
slab — it *stores* it. These two endpoints wrap the shard store
(shard_store.py) in pipeline terms:

  ProjectionSource  a projection shard store feeding the plan engine's
                    filter stage: `load(mesh)` scatter-reads exactly the
                    shards that overlap each rank's `input_sharding(mesh)`
                    slice (Eq. 5 load split) and returns the sharded device
                    array the engine consumes. With `codec=` at write time
                    the store persists the stream codec's WIRE format —
                    quantized shards plus, for scaled codecs (fp8), a
                    per-projection f32 scale sidecar store at
                    `<path>/scales` — and `load` decodes back to f32;
                    `load_encoded` returns the wire-format pair verbatim
                    (bit-exact round-trip, see tests/test_shard_store.py).
  VolumeSink        the paper's PFS store: `write(volume)` streams each
                    rank's slab (each addressable shard of the engine's
                    output — x over `model`, plus y over `data` with a
                    scatter reduce) to its own file.

Both are wired as optional `source=` / `sink=` stages on
`ReconstructionPlan.build()` (core/plan.py), closing the pipeline:

    src = ProjectionSource.write(dir_in, projections, chunks=(n_ranks, 1, 1))
    fdk = plan.build(source=src, sink=VolumeSink(dir_out))
    volume = fdk()          # load -> filter -> gather -> BP -> reduce -> store
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from functools import lru_cache
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import Precision, resolve_precision
from repro.obs import metrics as _metrics
from repro.obs.trace import get_tracer

from . import shard_store

# Sub-store holding the per-projection f32 scale sidecar of an encoded
# projection store (sibling of the data store's `shards/` directory).
SCALES_DIR = "scales"


@lru_cache(maxsize=None)
def _jit_decode(codec_name: str):
    """One jitted decode per codec, cached for the process: `load()` used to
    wrap `codec.decode` in a fresh `jax.jit` per call, retracing on every
    load. jit's own signature cache handles distinct input shapes (deltas of
    different sizes) under the one cached callable."""
    return jax.jit(Precision(codec_name).codec.decode)


class ProjectionSource:
    """Projections stored shard-per-file (raw f32, or a stream codec's wire
    format + scale sidecar), restorable onto any mesh."""

    def __init__(self, path: str):
        self.path = path
        self._consumed: set = set()   # shard files already folded (poll API)

    @classmethod
    def write(cls, path: str, projections,
              chunks: Optional[Sequence[int]] = None,
              codec: "Precision | str | None" = None) -> "ProjectionSource":
        """Lay projections down as a shard store. For a device array the
        files follow its sharding; for a host array pass e.g.
        ``chunks=(n_ranks, 1, 1)`` for the paper's slice-per-rank layout.

        `codec` (a storage-precision name, e.g. "fp8_e4m3") persists the
        stream codec's wire format instead of the input dtype: the data
        store holds the quantized shards (its manifest records the codec),
        and scaled codecs add a `<path>/scales` sidecar store with one f32
        scale per projection — fp8 shrinks the on-disk stream to a quarter
        of f32, the same trade the AllGather makes.
        """
        if codec is None:
            shard_store.save_array(path, projections, chunks=chunks)
            return cls(path)
        prec = resolve_precision(codec)
        data, scales = prec.codec.encode(jnp.asarray(projections))
        shard_store.save_array(path, data, chunks=chunks,
                               extra_manifest={"codec": prec.storage})
        if scales is not None:
            shard_store.save_array(os.path.join(path, SCALES_DIR),
                                   np.asarray(scales),
                                   chunks=None if chunks is None
                                   else chunks[:1])
        return cls(path)

    @property
    def shape(self) -> tuple:
        return tuple(shard_store.read_manifest(self.path)["shape"])

    @property
    def dtype(self) -> np.dtype:
        return shard_store.dtype_from_name(
            shard_store.read_manifest(self.path)["dtype"])

    @property
    def codec_name(self) -> Optional[str]:
        """Storage codec the store was encoded with (None = raw store)."""
        return shard_store.read_manifest(self.path).get("codec")

    def load_encoded(self):
        """The stored wire-format pair (data, scales) as host arrays —
        verbatim bytes, no decode. scales is None for raw/scale-free
        stores. The bit-exact-round-trip accessor."""
        data = shard_store.load_array(self.path)
        spath = os.path.join(self.path, SCALES_DIR)
        scales = (shard_store.load_array(spath)
                  if os.path.exists(os.path.join(spath,
                                                 shard_store.MANIFEST))
                  else None)
        return data, scales

    def load(self, mesh=None) -> jax.Array:
        """Scatter-read the projections for `mesh` (each rank's slice of the
        leading projection axis); the whole array on one device if None.
        Encoded stores are decoded back to f32 (quantized data x scale
        sidecar) after the scatter read — each rank only ever reads and
        dequantizes its own slice of the wire bytes.

        Traced as ``stage.read.copy`` (the store's bytes into host memory)
        then ``stage.read.h2d`` (those bytes onto the devices, fenced); the
        decode of an encoded store follows both."""
        codec_name = self.codec_name
        tracer = get_tracer()
        if mesh is None:
            with tracer.span("stage.read.copy"):
                host = self.load_encoded()
            with tracer.span("stage.read.h2d") as sp:
                data, scales = sp.fence(jax.device_put(host))
        else:
            from jax.sharding import NamedSharding
            from repro.core.distributed import _proj_spec, input_sharding

            # The sidecar is sharded along the projection axis exactly like
            # the data (one scale per projection): each rank scatter-reads
            # only its own slice, not the whole sidecar.
            shardings = {self.path: input_sharding(mesh)}
            spath = os.path.join(self.path, SCALES_DIR)
            if os.path.exists(os.path.join(spath, shard_store.MANIFEST)):
                shardings[spath] = NamedSharding(mesh, _proj_spec(mesh))
            with tracer.span("stage.read.copy"):
                host = {path: shard_store.read_shards(path, sharding)
                        for path, sharding in shardings.items()}
            with tracer.span("stage.read.h2d") as sp:
                arrays = sp.fence(
                    {path: shard_store.put_shards(host[path], sharding)
                     for path, sharding in shardings.items()})
            data, scales = arrays[self.path], arrays.get(spath)
        if codec_name is None:
            return data
        return _jit_decode(codec_name)(data, scales)

    # -- streaming discovery (the instant-CT source side) -------------------

    def poll(self) -> list:
        """Diff the store's (growing) manifest against what this source has
        already handed out: the contiguous [lo, hi) angle ranges of newly
        COMMITTED shards, sorted by lo. Read-only — ranges are marked
        consumed by `iter_deltas`, so repeated polls keep reporting a range
        until it is actually loaded. A store whose manifest does not exist
        yet (scanner not started) reports no deltas."""
        try:
            m = shard_store.read_manifest(self.path)
        except shard_store.StoreError:
            return []
        dtype = shard_store.dtype_from_name(m["dtype"])
        ready = []
        for entry in m["shards"]:
            if entry["file"] in self._consumed:
                continue
            idx = tuple(tuple(b) for b in entry["index"])
            fpath = os.path.join(self.path, shard_store.SHARD_DIR,
                                 entry["file"])
            # The manifest entry is the writer's commit point
            # (shard_store.append_region); the size check just refuses to
            # hand out a range whose bytes a non-protocol writer truncated.
            expected = dtype.itemsize
            for lo, hi in idx:
                expected *= hi - lo
            if (not os.path.exists(fpath)
                    or os.path.getsize(fpath) != expected):
                continue
            ready.append((idx[0][0], idx[0][1], entry["file"]))
        ready.sort()
        return [(lo, hi) for lo, hi, _ in ready]

    def load_slice(self, lo: int, hi: int, mesh=None) -> jax.Array:
        """Load + decode the angle range [lo, hi) only: the region read
        opens just the shard files (and sidecar shards) intersecting it.
        With a mesh the delta lands sharded with `input_sharding(mesh)` —
        ready for `IncrementalSession.update`."""
        shape = self.shape
        region = ((lo, hi),) + tuple((0, d) for d in shape[1:])
        data = shard_store.read_region(self.path, region)
        codec_name = self.codec_name
        scales = None
        if codec_name is not None:
            spath = os.path.join(self.path, SCALES_DIR)
            if os.path.exists(os.path.join(spath, shard_store.MANIFEST)):
                scales = jnp.asarray(
                    shard_store.read_region(spath, ((lo, hi),)))
        if mesh is not None:
            from repro.core.distributed import input_sharding
            data = jax.device_put(data, input_sharding(mesh))
        else:
            data = jnp.asarray(data)
        if codec_name is None:
            return data
        return _jit_decode(codec_name)(data, scales)

    def iter_deltas(self, mesh=None
                    ) -> Iterator[Tuple[int, int, jax.Array]]:
        """Consume newly committed deltas: yields (lo, hi, projections) for
        each range `poll()` discovers, decoded and (on a mesh) sharded, and
        marks it consumed — the discovery protocol IncrementalSession.poll
        drives. Yields nothing when the scanner has not committed anything
        new."""
        try:
            m = shard_store.read_manifest(self.path)
        except shard_store.StoreError:
            return
        by_range = {
            (tuple(e["index"][0][:2])): e["file"] for e in m["shards"]}
        for lo, hi in self.poll():
            delta = self.load_slice(lo, hi, mesh)
            # Mark consumed BEFORE yielding: the delta is fully loaded by
            # now, and a consumer that breaks (or errors) after receiving
            # it closes this generator — marking after the yield would
            # never run, so the already-folded range would be re-reported
            # by the next poll() and trip the session's overlap rejection.
            # A load_slice failure still leaves the range unconsumed
            # (retryable).
            self._consumed.add(by_range[(lo, hi)])
            yield lo, hi, delta


class StreamingProjectionWriter:
    """The scanner side of the streaming protocol: append projection deltas
    to a growing store that `ProjectionSource.poll()` discovers.

    Commit ordering (PFS-safe, see shard_store.append_region): for scaled
    codecs the scale sidecar lands and commits FIRST, then the data shard —
    whose manifest entry is the overall commit point. A reader that sees a
    committed data range is therefore guaranteed its scales are readable;
    a crash between the two leaves only an orphaned sidecar entry, which no
    reader ever addresses.

        writer = StreamingProjectionWriter(path, (N_p, N_v, N_u),
                                           codec="fp8_e4m3")
        writer.append(frames, lo)            # one scanner burst
        ...
        src = ProjectionSource(path)         # reader, possibly another host
        for lo, hi, delta in src.iter_deltas(mesh): session.update(...)
    """

    def __init__(self, path: str, shape: Sequence[int],
                 codec: "Precision | str | None" = None):
        if len(shape) != 3:
            raise ValueError(f"projection stream shape must be "
                             f"(N_p, N_v, N_u), got {tuple(shape)}")
        self.path = path
        self.shape = tuple(shape)
        self._prec = None if codec is None else resolve_precision(codec)
        extra = ({"codec": self._prec.storage}
                 if self._prec is not None else None)
        dtype = (np.float32 if self._prec is None
                 else self._prec.storage_dtype)
        shard_store.init_store(path, self.shape, dtype, extra_manifest=extra)
        if self._prec is not None and self._prec.codec.has_scales:
            shard_store.init_store(os.path.join(path, SCALES_DIR),
                                   self.shape[:1], np.float32)

    def append(self, projections, lo: int) -> Tuple[int, int]:
        """Commit the contiguous angle range [lo, lo + n) (encoding it
        first when the store carries a codec). Returns (lo, hi)."""
        projections = np.asarray(projections)
        n, n_v, n_u = projections.shape
        hi = lo + n
        if (n_v, n_u) != self.shape[1:] or hi > self.shape[0]:
            raise ValueError(
                f"delta [{lo}, {hi}) x ({n_v}, {n_u}) does not fit the "
                f"declared stream shape {self.shape}")
        region = ((lo, hi), (0, n_v), (0, n_u))
        if self._prec is None:
            shard_store.append_region(self.path, region, projections)
            return lo, hi
        data, scales = self._prec.codec.encode(jnp.asarray(projections))
        if scales is not None:   # sidecar first — see commit ordering above
            shard_store.append_region(os.path.join(self.path, SCALES_DIR),
                                      ((lo, hi),), np.asarray(scales))
        shard_store.append_region(self.path, region, np.asarray(data))
        return lo, hi


# Manifest key recording a non-canonical stored volume layout (VolumeSink).
LAYOUT_KEY = "layout"


class VolumeSink:
    """Slice-per-rank volume store: each shard of the reconstructed volume
    goes straight to its own file — no gather, no root writer."""

    def __init__(self, path: str):
        self.path = path

    def write(self, volume, layout: Optional[dict] = None) -> str:
        """Write the (sharded) volume; returns the store directory.

        `layout` records a NON-canonical engine layout in the manifest so
        `read()` can restore the canonical (N_x, N_y, N_z) volume — the
        chunked+scatter engine streams its internal 4-D
        (N_x, y_chunks, N_y/y_chunks, N_z) accumulator layout, recorded as
        ``{"kind": "y_chunk_major", "y_chunks": int}``. Without the record
        a reader had no way to tell the store was not a plain volume.

        Traced as ``stage.write.d2h`` (the per-shard device_get) then
        ``stage.write.file`` (the shard files and the manifest)."""
        extra = None if layout is None else {LAYOUT_KEY: layout}
        tracer = get_tracer()
        with tracer.span("stage.write.d2h"):
            host = shard_store.snapshot(volume)
        with tracer.span("stage.write.file"):
            return shard_store.save_array(self.path, host,
                                          extra_manifest=extra)

    def layout(self) -> Optional[dict]:
        """The recorded engine layout, or None for a canonical store."""
        return shard_store.read_manifest(self.path).get(LAYOUT_KEY)

    def read(self, sharding=None):
        """Read the stored volume back (host numpy, or scatter-read onto
        `sharding`), restoring the canonical (N_x, N_y, N_z) axis order
        when the manifest records a non-canonical engine layout. Device
        reads (`sharding=`) address the stored layout directly — resharding
        canonicalized data is the caller's concern."""
        arr = shard_store.load_array(self.path, sharding)
        layout = self.layout()
        if layout is None or sharding is not None:
            return arr
        kind = layout.get("kind")
        if kind != "y_chunk_major":
            raise shard_store.StoreError(
                f"volume store {self.path!r} records unknown layout "
                f"{kind!r}; cannot canonicalize")
        # (N_x, y_chunks, yc, N_z) -> (N_x, N_y, N_z): chunk-major y is
        # contiguous, a reshape restores the volume.
        n_x, y_chunks, yc, n_z = arr.shape
        return np.ascontiguousarray(arr).reshape(n_x, y_chunks * yc, n_z)

    def nbytes(self) -> int:
        """Stored payload size (shard files only, not the manifest)."""
        sdir = os.path.join(self.path, shard_store.SHARD_DIR)
        return sum(os.path.getsize(os.path.join(sdir, f))
                   for f in os.listdir(sdir))


# ---------------------------------------------------------------------------
# Inter-scan I/O overlap (repro/service): the paper overlaps filtering with
# back-projection *within* one scan; a serving loop lifts the same idea to
# the scan level — scan k+1's PFS reads and scan k-1's writes run on
# background threads while scan k computes. Device dispatch stays on the
# caller's thread; these helpers only move the host-side I/O off it.
# ---------------------------------------------------------------------------

class PrefetchError(RuntimeError):
    """A background load failed; raised on the consumer thread by
    `SourcePrefetcher.get` with the original exception as __cause__."""


class SourcePrefetcher:
    """Double-buffered background loader for a sequence of projection reads.

    jobs  : sequence of zero-arg callables, each returning one scan's
            projections (typically `lambda: source.load(mesh)` — a PFS
            scatter-read + decode). Jobs run IN ORDER on one worker thread.
    depth : how many loaded scans may sit ready ahead of the consumer
            (default 2 = classic double buffering: scan k+1 loads while
            scan k computes; memory stays bounded at `depth` scans).
    persistent : keep the worker alive after the initial jobs drain so
            `extend(jobs)` can feed it more work — the serve-loop mode
            (ReconstructionService.serve() runs ONE prefetcher across all
            drain passes instead of paying a thread spawn/join per pass).
            A persistent prefetcher only reaches DONE via `finish()` or
            `close()`; a one-shot one (the default) is finished at
            construction, exactly the pre-loop contract.

    State machine (DESIGN.md §Serving):

        IDLE --start()--> FILLING --queue full--> BLOCKED(producer)
        FILLING/BLOCKED --get()--> FILLING        consumer frees a slot
        persistent + jobs drained --> IDLE(worker) --extend()--> FILLING
        last job done after finish()/one-shot ctor --> DRAINING
            --get() x k--> DONE (StopIteration, LATCHED: every later
            get() raises StopIteration again instead of blocking on the
            empty queue forever)
        close() --> DONE (worker unblocked + joined; pending jobs
            abandoned; later get() raises StopIteration)
        job raises --> the error is queued in-order and re-raised by the
                       MATCHING get(); later jobs still run, so one bad
                       load fails only its own scan and the queue stays
                       positionally aligned (job k <-> get() k).

    Also iterable: ``for proj in SourcePrefetcher(jobs): ...``.
    """

    _DONE = object()

    def __init__(self, jobs: Sequence[Callable[[], object]] = (),
                 depth: int = 2, persistent: bool = False):
        if depth < 1:
            raise ValueError(f"prefetch depth={depth} must be >= 1")
        self._pending: "deque[Callable[[], object]]" = deque(jobs)
        self._jobs_cv = threading.Condition()
        self._no_more_jobs = not persistent   # one-shot: finished at ctor
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._started = False
        self._finished = False    # consumer-side latch: DONE was observed
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)

    def extend(self, jobs: Sequence[Callable[[], object]]) -> None:
        """Queue more load jobs on a `persistent` prefetcher (serve-loop
        reuse across drain passes). Raises on a finished/closed one —
        its worker is (or is about to be) gone."""
        with self._jobs_cv:
            if self._no_more_jobs or self._stop.is_set():
                raise RuntimeError(
                    "cannot extend a finished prefetcher (one-shot, "
                    "finish()ed, or closed)")
            self._pending.extend(jobs)
            self._jobs_cv.notify()

    def finish(self) -> None:
        """No more jobs are coming: after the pending ones drain, the
        worker queues DONE and exits (persistent mode's graceful end)."""
        with self._jobs_cv:
            self._no_more_jobs = True
            self._jobs_cv.notify()

    def _next_job(self):
        """Worker-side: the next job, or None when the prefetcher is done
        (stopped, or finished with nothing pending)."""
        with self._jobs_cv:
            while True:
                if self._stop.is_set():
                    return None
                if self._pending:
                    return self._pending.popleft()
                if self._no_more_jobs:
                    return None
                # persistent + idle: wait for extend()/finish()/close().
                # The timeout is a safety net against a lost notify.
                self._jobs_cv.wait(timeout=0.1)

    def _worker(self) -> None:
        # Metrics are re-fetched per job (not cached at start) so a
        # registry reset between drains cannot orphan the instruments.
        tracer = get_tracer()
        while True:
            job = self._next_job()
            if job is None:
                break
            try:
                with tracer.span("io.prefetch.load", timed=True) as sp:
                    item = (True, job())
                _metrics.counter("io.prefetch.loads").inc()
                _metrics.histogram("io.prefetch.load_seconds").observe(
                    sp.duration_s)
            except BaseException as e:  # re-raised on the consumer side
                item = (False, e)
                _metrics.counter("io.prefetch.errors").inc()
            if not self._put(item):
                break
        self._put((True, self._DONE))

    def _put(self, item) -> bool:
        """Blocking put that gives up when the consumer called close()."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                _metrics.gauge("io.prefetch.queue_depth").set(
                    self._q.qsize())
                return True
            except queue.Full:
                continue
        return False

    def start(self) -> "SourcePrefetcher":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def get(self):
        """Next loaded scan, blocking until the worker has it. Raises
        PrefetchError when that scan's load failed, StopIteration when all
        jobs are consumed — idempotently: exhaustion is latched, so calling
        get() again keeps raising StopIteration instead of deadlocking on
        the empty queue (the DONE sentinel is only ever queued once). get()
        after close() likewise raises StopIteration once the (abandoned)
        queue is drained."""
        self.start()
        if self._finished:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            try:
                ok, item = self._q.get(timeout=0.05)
                break
            except queue.Empty:
                # A closed prefetcher's worker may have died without
                # queueing DONE (close() makes _put give up); don't hang.
                if self._stop.is_set() and not self._thread.is_alive():
                    self._finished = True
                    raise StopIteration from None
        _metrics.gauge("io.prefetch.queue_depth").set(self._q.qsize())
        if item is not self._DONE:   # blocked-on-worker time, real items only
            _metrics.histogram("io.prefetch.wait_seconds").observe(
                time.perf_counter() - t0)
        if not ok:
            raise PrefetchError(
                f"background projection load failed: {item}") from item
        if item is self._DONE:
            self._finished = True
            raise StopIteration
        return item

    def __iter__(self):
        self.start()
        while True:
            try:
                yield self.get()
            except StopIteration:
                return

    def close(self) -> None:
        """Stop loading; pending jobs are abandoned (no partial results are
        handed out — even already-loaded ones still sitting in the queue)
        and later get() calls raise StopIteration."""
        self._stop.set()
        with self._jobs_cv:
            self._jobs_cv.notify()
        if self._started:
            self._thread.join(timeout=5.0)
        self._finished = True


class AsyncWriteback:
    """Write-behind executor for VolumeSink stores.

    `submit(sink, volume)` returns immediately after handing the finished
    (device) volume to a single-worker executor; the device->host transfer
    and the shard-per-file write happen off the compute thread, so scan
    k-1's store overlaps scan k's dispatch. Writes run in submission order
    (one worker). `pending` is bounded: submit blocks once more than
    `max_pending` volumes are in flight, so host memory stays bounded under
    a fast producer. `drain()` joins and re-raises the FIRST failed write
    (a serving loop must not ack scans whose stores failed silently).
    """

    def __init__(self, max_pending: int = 2):
        if max_pending < 1:
            raise ValueError(f"max_pending={max_pending} must be >= 1")
        self._max_pending = max_pending
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="volume-writeback")
        self._futures: List[Future] = []
        self._lock = threading.Lock()

    @property
    def pending(self) -> int:
        with self._lock:
            return sum(not f.done() for f in self._futures)

    def submit(self, sink: VolumeSink, volume,
               layout: Optional[dict] = None) -> Future:
        """Queue `sink.write(volume, layout=)`; blocks only when the
        write-behind queue is full (backpressure, not fire-and-forget)."""
        while self.pending >= self._max_pending:
            # Wait on the oldest unfinished write (ordered single worker).
            with self._lock:
                oldest = next((f for f in self._futures if not f.done()),
                              None)
            if oldest is None:
                break
            try:
                oldest.result()
            except BaseException:
                pass  # surfaced by drain(); keep the queue moving

        def _counted_write():
            # Runs on the writeback worker thread: the span lands on its
            # own tid in the trace, visualizing store/compute overlap.
            t0 = time.perf_counter()
            try:
                with get_tracer().span("io.writeback.write"):
                    out = sink.write(volume, layout=layout)
            except BaseException:
                _metrics.counter("io.writeback.errors").inc()
                raise
            finally:
                _metrics.gauge("io.writeback.pending").set(self.pending)
            _metrics.counter("io.writeback.writes").inc()
            _metrics.histogram("io.writeback.write_seconds").observe(
                time.perf_counter() - t0)
            return out

        fut = self._pool.submit(_counted_write)
        with self._lock:
            # Prune completed-OK writes here, not only in drain(): callers
            # that result() the returned future directly (the service's
            # per-ticket join) would otherwise grow the list forever.
            # Failed futures are kept so drain() can still re-raise them.
            self._futures = [f for f in self._futures
                             if not f.done() or f.exception() is not None]
            self._futures.append(fut)
        _metrics.gauge("io.writeback.pending").set(self.pending)
        return fut

    def drain(self) -> int:
        """Wait for every queued write; returns how many completed OK and
        re-raises the first failure (subsequent writes still ran — the
        single worker never cancels queued work)."""
        with self._lock:
            futures, self._futures = self._futures, []
        first_err = None
        done = 0
        for f in futures:
            try:
                f.result()
                done += 1
            except BaseException as e:
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        return done

    def close(self) -> None:
        self._pool.shutdown(wait=True)
