"""Sharded, async, reshard-on-restore checkpointing.

The reference promises "resume the training" after failures (README.md:27)
with no mechanism; for TPU elasticity the checkpoint layer is the linchpin
(SURVEY.md §5.4, §7): a save taken on an 8-chip mesh must restore onto a
32-chip mesh (and vice versa) without materialising full arrays on any single
host.

Layout (one directory per step)::

    <dir>/step_00000010/
        manifest.json            # leaf keys, shapes, dtypes, mesh meta
        leaf_00003/0-128_0-64.npy   # chunk covering [0:128, 0:64]
        ...
        COMMITTED                # written last — step is valid iff present

Mechanics:
- **save**: every process writes the chunks for its addressable, replica-0
  shards (`jax.Array.addressable_shards`), so write bandwidth scales with
  hosts and nothing is gathered. Host copies are snapshotted synchronously
  (donation-safe), chunk IO runs on a background thread.
- **restore**: ``jax.make_array_from_callback`` asks for exactly the slices
  the *new* sharding places on local devices; the reader assembles them from
  whichever chunks overlap, so an 8→32 or 32→8 reshard reads only what each
  host needs (memory-mapped on POSIX).
- **storage**: chunk IO is pluggable (core/storage.py). POSIX backends
  commit by renaming per-process tmp dirs into the step dir (atomic rename);
  object stores (``gs://``) write chunks directly to their final keys —
  atomic puts — and commit is marker-after-all-puts, ordered by a collective
  barrier. The ``directory`` argument is a URL; plain paths mean POSIX.
"""

from __future__ import annotations

import json
import re
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from easydl_tpu.core.chunk_cache import ChunkCache
from easydl_tpu.core.storage import CheckpointStorage, get_storage
from easydl_tpu.utils.logging import get_logger

log = get_logger("core", "checkpoint")

_STEP_RE = re.compile(r"^step_(\d{8})$")
_COMMITTED = "COMMITTED"
#: written by quarantine(): the step's bytes proved unreadable at restore
#: time (truncated chunk, bad manifest). Kept alongside the demoted dir so
#: operators can autopsy it; a later re-save of the same step clears the
#: whole dir through the ordinary uncommitted-debris path.
_CORRUPT = "CORRUPT"


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _chunk_name(index: Tuple[slice, ...], shape: Tuple[int, ...]) -> str:
    if not shape:
        return "scalar.npy"
    parts = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        parts.append(f"{start}-{stop}")
    return "_".join(parts) + ".npy"


def _parse_chunk_name(name: str) -> Optional[List[Tuple[int, int]]]:
    if name == "scalar.npy":
        return []
    if not name.endswith(".npy"):
        return None
    try:
        return [
            (int(a), int(b))
            for a, b in (p.split("-") for p in name[:-4].split("_"))
        ]
    except ValueError:
        return None


class _LeafReader:
    """Assembles arbitrary slices of one leaf from its saved chunks.

    With a host-local :class:`ChunkCache` and this save's token, chunk loads
    try tmpfs first — the survivor fast path: a rank whose host wrote a
    chunk reads it back from memory; only chunks other hosts wrote (i.e.
    slices that actually moved in a reshard) hit shared storage."""

    def __init__(self, storage: CheckpointStorage, leaf_dir: str,
                 shape: Tuple[int, ...], dtype: np.dtype,
                 cache: Optional[ChunkCache] = None, cache_token: str = "",
                 cache_rel: str = ""):
        self.storage = storage
        self.shape = shape
        self.dtype = dtype
        self._cache = cache
        self._cache_token = cache_token
        self._cache_rel = cache_rel
        self._chunks: List[Tuple[List[Tuple[int, int]], str, str]] = []
        # make_array_from_callback calls read() once per local device; on
        # object stores each uncached load_array is a full HTTP download, so
        # overlapping device slices would re-fetch the same chunk per device.
        # The reader lives only for one leaf's restore — the cache is small
        # and short-lived. (POSIX load_array returns an mmap: caching it
        # just keeps the fd.)
        self._loaded: Dict[str, np.ndarray] = {}
        # Chunk inventory is the union of storage and cache listings: after
        # a same-host restart the cache alone can carry the whole leaf, and
        # the token gate (manifest-recorded) makes cached names as
        # authoritative as stored ones.
        names = set(storage.listdir(leaf_dir))
        if cache is not None:
            names.update(
                n for n in cache.listdir(cache_token, cache_rel)
                if not n.endswith(".tmp"))
        for name in sorted(names):
            bounds = _parse_chunk_name(name)
            if bounds is not None:
                self._chunks.append((bounds, f"{leaf_dir}/{name}", name))
        if not self._chunks:
            raise FileNotFoundError(f"no chunks in {leaf_dir}")

    def _load(self, path: str, name: str) -> np.ndarray:
        arr = self._loaded.get(path)
        if arr is None:
            if self._cache is not None:
                arr = self._cache.load(self._cache_token,
                                       f"{self._cache_rel}/{name}")
            if arr is None:
                arr = self.storage.load_array(path)
            self._loaded[path] = arr
        return arr

    def read(self, index: Tuple[slice, ...]) -> np.ndarray:
        if not self.shape:
            return self._load(*self._chunks[0][1:])
        want = [
            (0 if sl.start is None else sl.start, dim if sl.stop is None else sl.stop)
            for sl, dim in zip(index, self.shape)
        ]
        for bounds, path, name in self._chunks:
            if bounds == want:
                # exact-chunk hit (the same-sharding restore): hand the
                # mmap/array straight through — no assembly copy
                return self._load(path, name)
        out = np.empty([b - a for a, b in want], dtype=self.dtype)
        filled = 0
        for bounds, path, name in self._chunks:
            # overlap of chunk bounds with wanted region
            inter = [
                (max(a, ca), min(b, cb))
                for (a, b), (ca, cb) in zip(want, bounds)
            ]
            if any(a >= b for a, b in inter):
                continue
            data = self._load(path, name)
            src = tuple(
                slice(a - ca, b - ca) for (a, b), (ca, cb) in zip(inter, bounds)
            )
            dst = tuple(
                slice(a - wa, b - wa) for (a, b), (wa, wb) in zip(inter, want)
            )
            out[dst] = data[src]
            filled += int(np.prod([b - a for a, b in inter]))
        if filled != out.size:
            raise ValueError(
                f"chunks cover {filled}/{out.size} elements of requested slice "
                f"{want} (shape {self.shape})"
            )
        return out


class CheckpointManager:
    """Save/restore sharded pytrees, keeping the last ``keep`` committed steps.

    ``directory`` is a URL: a plain path (or ``file://``) selects the POSIX
    backend; ``gs://bucket/prefix`` the object-store backend.
    """

    def __init__(self, directory: str, keep: int = 3, async_save: bool = True,
                 storage: Optional[CheckpointStorage] = None,
                 on_event: Optional[Callable[..., None]] = None):
        self.directory = directory
        #: ``on_event(name, **data)`` is told where a save stands:
        #: ``ckpt_snapshot_done`` (the synchronous device-to-host copies
        #: are over and the caller's step loop goes on: ``seconds`` they
        #: took, ``waited_s`` spent before them on the save before this
        #: one, ``bytes``, ``leaves``), ``ckpt_chunks_written`` (every chunk
        #: of this process is in storage: ``seconds`` the writing took,
        #: ``bytes``) and ``ckpt_committed`` (the marker is written:
        #: ``seconds`` since ``save`` was entered). Each carries ``step``.
        #: The last two come from the IO thread of an async save. The
        #: elastic worker puts them on its timeline; a callback that raises
        #: fails the save like any other error in it.
        self._on_event = on_event
        self.keep = keep
        self.async_save = async_save
        self.storage = storage if storage is not None else get_storage(directory)
        #: host-local tmpfs cache (core/chunk_cache.py): same-host restores
        #: read back this host's own chunk writes from memory instead of
        #: shared storage — the generation-switch restore fast path. Cache
        #: retention tracks checkpoint retention: every restorable step
        #: should be cache-servable, not just the newest two.
        self.cache = ChunkCache.for_directory(directory, keep=keep)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # Multi-process async saves split in two: chunk IO runs on a
        # background thread (no collectives), while the commit — whose
        # barriers are collectives and must run on the main thread — is
        # deferred until :meth:`finalize` (or :meth:`wait`) is called from
        # the training loop at a later step boundary.
        self._pending_commit = None
        self._in_flight = False
        self.storage.makedirs("")

    @property
    def in_flight(self) -> bool:
        """An asynchronous save has returned and is not committed yet: true
        from ``save()``'s return until its ``ckpt_committed`` (or until its
        failure is known). The steps a loop runs meanwhile share the host
        with the chunk writer; the elastic worker stamps each step record
        with this (``commit_in_flight``)."""
        return self._in_flight

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: Any, metadata: Optional[Dict[str, Any]] = None) -> None:
        """Snapshot shards to host, then write asynchronously (unless
        ``async_save=False``). Call :meth:`wait` before donating buffers is
        NOT needed — the snapshot happens here, synchronously. In
        multi-process runs an async save defers its commit barrier: call
        :meth:`finalize` each step (all ranks together) to complete it."""
        t_enter = time.perf_counter()
        self.wait()
        waited_s = time.perf_counter() - t_enter
        storage = self.storage
        multiproc = jax.process_count() > 1
        # Skip if already committed (e.g. quiesce landing on a periodic-save
        # step). The decision must be COLLECTIVE: with per-process storage
        # views (GCS/NFS lag) some ranks could skip while others enter the
        # save's barriers and hang — so process 0's verdict is broadcast.
        skip = step in self.steps()
        if multiproc:
            from jax.experimental import multihost_utils

            skip = bool(
                multihost_utils.broadcast_one_to_all(np.asarray(skip, np.int32))
            )
        if skip:
            log.info("step %d already checkpointed; skipping", step)
            return
        # Per-save cache token: leading step number keeps token dirs
        # sortable for GC; the uuid suffix makes chunks from an aborted save
        # of the SAME step unservable (different token). Rank 0's token is
        # broadcast so every rank caches under the name the manifest records.
        cache_token = f"{step:08d}-{uuid.uuid4().hex[:12]}"
        if multiproc:
            from jax.experimental import multihost_utils

            raw = np.frombuffer(cache_token.encode().ljust(32), np.uint8)
            cache_token = bytes(
                np.asarray(multihost_utils.broadcast_one_to_all(raw))
            ).decode().strip()
        t_snapshot = time.perf_counter()
        leaves = jax.tree_util.tree_flatten_with_path(state)[0]
        snapshot = []  # (leaf_idx, keystr, global_shape, dtype, [(bounds, np.ndarray)])
        for i, (path, leaf) in enumerate(leaves):
            key = _keystr(path)
            if isinstance(leaf, jax.Array):
                shape, dtype = tuple(leaf.shape), np.dtype(leaf.dtype)
                chunks = []
                for shard in leaf.addressable_shards:
                    if shard.replica_id != 0:
                        continue
                    chunks.append((shard.index, np.asarray(shard.data)))
                snapshot.append((i, key, shape, dtype, chunks))
            else:
                arr = np.asarray(leaf)
                snapshot.append(
                    (i, key, tuple(arr.shape), arr.dtype,
                     [(tuple(slice(0, d) for d in arr.shape), arr)])
                )

        snapshot_bytes = sum(data.nbytes for _, _, _, _, chunks in snapshot
                             for _, data in chunks)
        self._event("ckpt_snapshot_done", step=step,
                    seconds=time.perf_counter() - t_snapshot,
                    waited_s=waited_s, bytes=snapshot_bytes,
                    leaves=len(snapshot))
        t0 = time.perf_counter()
        step_dir = f"step_{step:08d}"
        # POSIX: stage in a per-process tmp dir, commit by rename.
        # Object store: write straight to the final keys (puts are atomic and
        # restore gates on the marker) — but then debris from an aborted save
        # at this step must be cleared BEFORE any rank writes, not at commit.
        direct = not storage.atomic_rename
        write_dir = step_dir if direct else step_dir + f".tmp.{jax.process_index()}"
        if direct:
            if jax.process_index() == 0 and self._uncommitted_debris(step_dir):
                log.warning("clearing aborted save at %s", step_dir)
                storage.delete_tree(step_dir)
            if multiproc:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(f"easydl_ckpt_clean_{step}")

        def write_chunks():
            # Chunk IO only (no collectives) — safe on a background thread.
            if not direct:
                # Our own tmp dir may hold chunks from a save that crashed
                # mid-way (possibly under a different sharding); the commit
                # loop moves every file in it, so start from a clean slate.
                # Per-process dir — a local decision, no barrier needed.
                storage.delete_tree(write_dir)
                storage.makedirs(write_dir)
            manifest = {
                "step": step,
                "metadata": metadata or {},
                "cache_token": cache_token,
                "leaves": [
                    {"index": i, "key": key, "shape": list(shape), "dtype": str(dtype)}
                    for i, key, shape, dtype, _ in snapshot
                ],
            }
            for i, key, shape, dtype, chunks in snapshot:
                leaf_dir = f"{write_dir}/leaf_{i:05d}"
                storage.makedirs(leaf_dir)
                for index, data in chunks:
                    name = _chunk_name(index, shape)
                    storage.save_array(f"{leaf_dir}/{name}", data)
                    if self.cache is not None:
                        self.cache.put(cache_token, f"leaf_{i:05d}/{name}",
                                       data)
            if jax.process_index() == 0:
                storage.write_bytes(
                    f"{write_dir}/manifest.json", json.dumps(manifest).encode()
                )
            self._event("ckpt_chunks_written", step=step,
                        seconds=time.perf_counter() - t0,
                        bytes=snapshot_bytes)

        def commit():
            # Contains the collective barriers — must run on the MAIN thread
            # in multi-process runs (via finalize()/wait() or the sync path).
            if not direct:
                # A step_dir without COMMITTED is debris from an aborted save
                # (we may be retraining through the same step after a
                # restore): clear it so stale chunks can't mix into — or
                # block — this commit. Process 0 decides and clears; the
                # barrier is UNCONDITIONAL in multi-process runs so every
                # rank enters the same collectives regardless of its local
                # FS view.
                if jax.process_index() == 0 and self._uncommitted_debris(step_dir):
                    log.warning("clearing aborted save at %s", step_dir)
                    storage.delete_tree(step_dir)
                if multiproc:
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        f"easydl_ckpt_clean_{step}"
                    )
                # Single-host commit: rename tmp → final. Multi-host: every
                # process renames its own tmp dir contents in.
                if jax.process_count() == 1:
                    storage.rename(write_dir, step_dir)
                else:
                    storage.makedirs(step_dir)
                    for name in storage.listdir(write_dir):
                        src, dst = f"{write_dir}/{name}", f"{step_dir}/{name}"
                        if storage.isdir(src):
                            storage.makedirs(dst)
                            for chunk in storage.listdir(src):
                                storage.rename(f"{src}/{chunk}", f"{dst}/{chunk}")
                        else:
                            storage.rename(src, dst)
                    storage.delete_tree(write_dir)
            if multiproc:
                # Every process has written/renamed its chunks in; only then
                # may the marker appear (restore treats COMMITTED as "all
                # shards present").
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(f"easydl_ckpt_{step}")
            if jax.process_index() == 0:
                storage.write_bytes(f"{step_dir}/{_COMMITTED}", str(step).encode())
            log.info("saved step %d in %.2fs -> %s/%s",
                     step, time.perf_counter() - t0, self.directory, step_dir)
            self._in_flight = False
            self._event("ckpt_committed", step=step,
                        seconds=time.perf_counter() - t_enter)
            self._gc()
            if self.cache is not None:
                self.cache.gc()

        if self.async_save:
            def run_io():
                try:
                    write_chunks()
                    if not multiproc:
                        # No collectives involved — commit on the IO thread
                        # so single-process saves complete with no further
                        # calls (pre-existing contract).
                        commit()
                except BaseException as e:  # surfaced on next wait()/save()
                    self._error = e
                    self._in_flight = False

            if multiproc:
                self._pending_commit = commit
            self._in_flight = True
            self._thread = threading.Thread(target=run_io, daemon=True)
            self._thread.start()
        else:
            write_chunks()
            commit()

    def _event(self, name: str, **data: Any) -> None:
        if self._on_event is not None:
            self._on_event(name, **data)

    def _uncommitted_debris(self, step_dir: str) -> bool:
        return (
            bool(self.storage.listdir(step_dir))
            and not self.storage.exists(f"{step_dir}/{_COMMITTED}")
        )

    def finalize(self, block: bool = False) -> bool:
        """Complete a pending deferred commit, running its collective
        barriers on the caller's (main) thread.

        Multi-process contract: every process calls this at the same step
        boundary with the same ``block`` value. With ``block=False`` the
        commit happens only once ALL ranks' chunk IO has finished (agreed via
        a tiny allgather, so no rank enters the barrier alone). The allgather
        carries a tri-state (pending / ready / failed), not just completion:
        if any rank's chunk IO raised, EVERY rank drops the pending commit
        and raises instead of entering the commit collectives — otherwise the
        healthy ranks would hang in ``sync_global_devices`` waiting for the
        failed rank, until external failure detection killed the job.
        Returns True when nothing remains pending."""
        if self._pending_commit is None:
            return True
        # Reap the IO thread if finished (or block for it): joining is safe
        # here — the thread does chunk IO only, no collectives.
        if self._thread is not None and (block or not self._thread.is_alive()):
            self._thread.join()
            self._thread = None
        io_done = self._thread is None
        # 0 = chunk IO still running, 1 = ready to commit, 2 = IO failed.
        local = 2 if (io_done and self._error is not None) else int(io_done)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            states = multihost_utils.process_allgather(
                np.asarray([local], np.int32)
            )
            if int(states.max()) == 2:
                self._pending_commit = None
                self._in_flight = False
                if self._error is not None:
                    err, self._error = self._error, None
                    raise RuntimeError(
                        f"async checkpoint save failed: {err!r}"
                    ) from err
                raise RuntimeError(
                    "async checkpoint save failed on another process; "
                    "commit dropped on all ranks"
                )
            ready = bool(states.min() == 1)
        else:
            ready = local >= 1  # single-process: wait() raises on failure
        if not ready:
            return False
        self.wait()
        return True

    def wait(self) -> None:
        """Block until any in-flight save (IO + deferred commit) completes."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            self._pending_commit = None  # chunks incomplete: never commit
            raise RuntimeError(f"async checkpoint save failed: {err!r}") from err
        if self._pending_commit is not None:
            commit, self._pending_commit = self._pending_commit, None
            commit()

    # ---------------------------------------------------------------- restore
    def steps(self) -> List[int]:
        out = []
        for name in self.storage.listdir(""):
            m = _STEP_RE.match(name)
            if m and self.storage.exists(f"{name}/{_COMMITTED}"):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.steps()
        return steps[-1] if steps else None

    def metadata(self, step: int) -> Dict[str, Any]:
        return json.loads(
            self.storage.read_bytes(f"step_{step:08d}/manifest.json")
        )

    def restore(
        self,
        step: int,
        abstract_state: Any,
        shardings: Any,
    ) -> Any:
        """Rebuild ``abstract_state``'s tree with arrays sharded per
        ``shardings`` — which may describe a completely different mesh than
        the one that saved. Leaf matching is by tree-path key."""
        step_dir = f"step_{step:08d}"
        manifest = self.metadata(step)
        by_key = {leaf["key"]: leaf for leaf in manifest["leaves"]}

        flat_abs = jax.tree_util.tree_flatten_with_path(abstract_state)
        flat_shd = jax.tree_util.tree_flatten(shardings)[0]
        leaves_abs, treedef = flat_abs
        if len(flat_shd) != len(leaves_abs):
            raise ValueError(
                f"shardings tree has {len(flat_shd)} leaves, state has {len(leaves_abs)}"
            )
        out_leaves = []
        for (path, abs_leaf), sharding_ in zip(leaves_abs, flat_shd):
            key = _keystr(path)
            if key not in by_key:
                raise KeyError(f"checkpoint step {step} missing leaf {key}")
            rec = by_key[key]
            saved_shape = tuple(rec["shape"])
            want_shape = tuple(abs_leaf.shape)
            if saved_shape != want_shape:
                raise ValueError(
                    f"{key}: saved shape {saved_shape} != target {want_shape}"
                )
            dtype = np.dtype(rec["dtype"])
            reader = _LeafReader(
                self.storage, f"{step_dir}/leaf_{rec['index']:05d}",
                saved_shape, dtype,
                cache=self.cache,
                cache_token=manifest.get("cache_token", ""),
                cache_rel=f"leaf_{rec['index']:05d}",
            )
            arr = jax.make_array_from_callback(
                want_shape, sharding_, lambda idx, r=reader: r.read(idx)
            )
            if arr.dtype != abs_leaf.dtype:
                arr = arr.astype(abs_leaf.dtype)
            out_leaves.append(arr)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    # ------------------------------------------------------------ quarantine
    def quarantine(self, step: int) -> None:
        """Demote a committed step whose bytes failed to restore: write the
        CORRUPT marker first (evidence), then remove COMMITTED — after
        which :meth:`steps` no longer offers the step and the next
        :func:`restore_with_fallback` candidate is the previous one. Marker
        order matters: a crash between the two writes must leave the step
        either still-committed or visibly corrupt, never silently absent.

        Multi-process callers gate this to one process and barrier after
        (see elastic/worker.py) — the markers live in shared storage."""
        step_dir = f"step_{step:08d}"
        try:
            self.storage.write_bytes(f"{step_dir}/{_CORRUPT}",
                                     str(step).encode())
        except OSError as e:  # marker is evidence, not a gate
            log.warning("could not write corrupt marker for step %d: %s",
                        step, e)
        self.storage.delete_tree(f"{step_dir}/{_COMMITTED}")
        log.warning("quarantined checkpoint step %d (%s/%s)", step,
                    self.directory, step_dir)

    # -------------------------------------------------------------------- gc
    def _gc(self) -> None:
        if jax.process_index() != 0:
            return
        steps = self.steps()
        for old in steps[: -self.keep] if self.keep > 0 else []:
            step_dir = f"step_{old:08d}"
            # Marker first: a half-deleted step must read as uncommitted,
            # not as a committed step with missing chunks.
            self.storage.delete_tree(f"{step_dir}/{_COMMITTED}")
            self.storage.delete_tree(step_dir)


def restore_with_fallback(
    manager: CheckpointManager,
    restore_fn,
    agree_int=None,
    all_ok=None,
    quarantine=None,
    max_attempts: int = 8,
):
    """Restore the newest committed step, falling back past corrupt ones.

    The linchpin of the corrupted-checkpoint chaos scenario: a COMMITTED
    step whose bytes are damaged (truncated chunk, unreadable manifest)
    must cost one quarantine + one older restore, not a crash-loop. Loop:

    1. agree on the newest committed step (``agree_int`` broadcasts rank 0's
       candidate in multi-process runs — two ranks restoring different
       steps would split the world);
    2. every rank attempts ``restore_fn(step)``;
    3. ``all_ok`` agrees the verdict across ranks (corruption often bites
       only the ranks whose slices overlap the bad chunk — the survivors
       must discard their restored state and fall back WITH the victims,
       or they'd hang in the next collective);
    4. on any failure, ``quarantine(step)`` demotes the step (default:
       ``manager.quarantine`` — multi-process callers pass a rank-gated,
       barriered wrapper) and the loop retries one step older.

    Returns ``(state, step)``; ``(None, -1)`` means no restorable
    checkpoint (callers fresh-init, their pre-existing path). The defaults
    are the single-process wiring; elastic/worker.py supplies the
    collective versions."""
    agree_int = agree_int or (lambda v: v)
    all_ok = all_ok or (lambda ok: ok)
    quarantine = quarantine or manager.quarantine
    for _ in range(max_attempts):
        local = manager.latest_step()
        step = int(agree_int(-1 if local is None else local))
        if step < 0:
            return None, -1
        state = None
        try:
            state = restore_fn(step)
            ok = True
        except Exception as e:
            log.warning("restore of step %d failed: %r", step, e)
            ok = False
        if all_ok(ok):
            return state, step
        del state  # a survivor's state from a bad step must not leak
        quarantine(step)
    raise RuntimeError(
        f"no restorable checkpoint under {manager.directory} after "
        f"{max_attempts} quarantine fallbacks"
    )
