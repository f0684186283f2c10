"""Train-side flash-checkpoint engine.

Parity with the reference's CheckpointEngine
(dlrover/trainer/torch/flash_checkpoint/engine.py:75 —
save_to_memory:169 with the shm-lock + all-rank-ready barrier
:202-219), built for JAX:

* state is one *global* sharded pytree, not per-rank torch state_dicts;
  each process stages only the addressable shards it owns (replica 0 of
  each shard, so replicated leaves are written exactly once per shard);
* device→host keeps many transfers of a few MiB in flight at once
  (``_ReadAhead``): a large shard is laid out linearly on the device
  and cut into pieces, a bounded number of bytes ahead, and each piece
  is written into shm as it arrives. The analogue of the reference's
  GPU→CPU ``tensor.copy_`` into shm (2.3 s for 3 GB in
  docs/design/async-checkpoint.md); on a v5e, device idle, GPT-2
  124M's 1.244 GB reach the segment in 0.17 s (7.3 GB/s) and 7 GB of
  Mistral-width state in 1.04 s, where one ``np.asarray`` a shard and
  then the copy took 0.77 s and 4.57 s (PERF.md, PR 25). The buffers
  the transfers fill are kept on the C allocator's heap
  (``_keep_transfer_buffers_on_the_heap``): in a training loop GPT-2's
  save then stalls the step 0.16 s where it stalled it 0.18 or 0.30 s,
  by what the process had freed before, and 8.4 GB of Mistral-width
  state take 1.05 s for 1.18 (PERF.md, PR 47);
* persistence is delegated to the host agent via a SharedQueue event —
  the trainer never blocks on storage.

Restore reassembles global arrays from any shard layout and re-shards
onto the current mesh (reshard-on-load), covering the reference's FSDP
reshard-on-restart (atorch/utils/fsdp_save_util.py) by construction.
"""

from __future__ import annotations

import collections
import ctypes
import functools
import os
import resource
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from dlrover_tpu import obs
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.common import ckpt_shm
from dlrover_tpu.common.ckpt_shm import (
    SharedMemoryHandler,
    TensorEntry,
    plan_entries,
)
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedQueue,
)

logger = get_logger("flash_ckpt")

_CKPT_OPS = obs.counter(
    "dlrover_ckpt_ops_total",
    "Flash-checkpoint operations",
    ("op", "result"),
)
_CKPT_STAGE_SECONDS = obs.histogram(
    "dlrover_ckpt_stage_seconds",
    "Device-to-shm staging time of save_to_memory",
)
_CKPT_RESTORE_SECONDS = obs.histogram(
    "dlrover_ckpt_restore_seconds",
    "End-to-end restore time of CheckpointEngine.load",
)

# How a save reads the state off the device (``_ReadAhead``). A shard
# above _PIECE_BYTES goes to the host as linear pieces of about that
# size, so a piece written into the segment leaves its memory to a
# later one. Shards are started until _AHEAD_BYTES of them are on their
# way, so the read holds that much of device memory beyond the state,
# and while it cuts a shard up to twice that shard more. Measured on a
# v5e with GPT-2 124M's and Mistral's states, pieces of 2 to 32 MiB and
# 128 MiB to 2 GiB ahead (PERF.md, PR 25); not options.
_PIECE_BYTES = 8 << 20
_AHEAD_BYTES = 512 << 20

# Where the memory of those pieces comes from. The runtime ``malloc``s
# a host buffer for every transfer, and glibc serves a ``malloc`` from
# the heap below its mmap threshold and from a fresh mapping, whose
# pages fault in as they are filled, from it up. That threshold moves:
# it starts at 128 KiB and rises to the size of any mapped chunk the
# process frees, up to 32 MiB, and the trim threshold, above which
# glibc hands the heap's freed top back to the kernel, follows it at
# twice its value. So what a save cost hung on what the process had
# freed before its first one (the step's executable read back from
# the compile cache, for one): GPT-2 124M's stalled the step 175 ms
# or 300. ``_keep_transfer_buffers_on_the_heap`` pins the first at its
# ceiling, which also stops it moving, and raises the second (to the
# largest ``mallopt``'s C int holds), so that a piece is heap memory
# and its pages are still mapped at the next save: 155-160 ms either
# way. Each alone leaves one of the two modes slow (PERF.md, PR 47,
# both measured apart on a v5e); not options. The cost: up to
# _AHEAD_BYTES of freed buffers stay in the process between saves and
# are not handed back to the kernel. A user's own
# MALLOC_MMAP_THRESHOLD_ or MALLOC_TRIM_THRESHOLD_ in the environment
# wins.
_MMAP_THRESHOLD = 32 << 20
_TRIM_THRESHOLD = (2 << 30) - 1
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3  # <malloc.h>

CKPT_EVENT_QUEUE = "ckpt_events"
CKPT_STATUS_DICT = "ckpt_status"
TRACKER_FILE = "latest_checkpointed_step"
WRITING_PREFIX = "._writing_"


def _path_name(path) -> str:
    """'params/blocks/wqkv'-style stable leaf name from a key path."""
    import jax

    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def flatten_named(tree) -> List[Tuple[str, Any]]:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_path_name(path), leaf) for path, leaf in flat]


def step_dir(checkpoint_dir: str, step: int) -> str:
    return f"{checkpoint_dir.rstrip('/')}/{step}"


def writing_dir(checkpoint_dir: str, step: int) -> str:
    return f"{checkpoint_dir.rstrip('/')}/{WRITING_PREFIX}{step}"


def done_dir(checkpoint_dir: str, step: int) -> str:
    """Done-files live *outside* the writing dir so the commit rename
    doesn't destroy the evidence a retrying committer needs."""
    return f"{checkpoint_dir.rstrip('/')}/.done_{step}"


def pack_shard_file(step: int, entries: List[TensorEntry], extra: dict,
                    payload: bytes) -> bytes:
    meta = ckpt_shm.pack_meta(step, entries, extra)
    return (len(meta).to_bytes(8, "little") + meta + payload)


def unpack_shard_file(data: bytes) -> Tuple[int, List[TensorEntry],
                                            dict, bytes]:
    meta_len = int.from_bytes(data[:8], "little")
    step, entries, extra = ckpt_shm.unpack_meta(data[8:8 + meta_len])
    return step, entries, extra, data[8 + meta_len:]


@functools.cache
def _linear_pieces():
    """The jitted cut of one shard into linear pieces of ``size``
    elements (the last one shorter), on the shard's device. jax is not
    imported with this module: the agent's saver imports it too."""
    import jax

    def cut(x, size):
        flat = x.reshape(-1)
        return tuple(
            flat[at:at + size] for at in range(0, flat.shape[0], size)
        )

    return jax.jit(cut, static_argnums=1)


def _glibc_mallopt():
    """glibc's ``mallopt`` as a function of two ints, or None where the
    process's C library is another or has none."""
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version  # glibc alone has it
        mallopt = libc.mallopt
    except (OSError, AttributeError):
        return None
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    return mallopt


@functools.cache
def _keep_transfer_buffers_on_the_heap() -> None:
    """Pin the C allocator's mmap and trim thresholds (the note at
    ``_MMAP_THRESHOLD``), once a process (the cache is the guard): the
    first engine built does it, importing this module does not. A
    threshold the user set in the environment is left alone; where
    ``mallopt`` is missing or refuses, a save reads the device as it
    did before."""
    mallopt = _glibc_mallopt()
    glibc = mallopt is not None
    pinned = {"mmap_threshold": None, "trim_threshold": None}
    ok = glibc
    if glibc:
        for name, param, value in (
                ("mmap_threshold", _M_MMAP_THRESHOLD, _MMAP_THRESHOLD),
                ("trim_threshold", _M_TRIM_THRESHOLD, _TRIM_THRESHOLD)):
            env = f"MALLOC_{name.upper()}_"
            if env in os.environ:
                logger.info("%s is set in the environment: %s is left "
                            "to it", env, name)
            elif mallopt(param, value) == 1:
                pinned[name] = value
            else:
                ok = False
    if not ok:
        logger.warning(
            "the C allocator's thresholds are not pinned (%s): a save's "
            "transfer buffers may be fresh mappings",
            "mallopt refused" if glibc else "no glibc")
    obs.event("ckpt.heap_pinned", ok=ok,
              libc="glibc" if glibc else "other", **pinned)


def _minor_faults() -> int:
    """Pages this process has had mapped in without I/O so far: a save
    that fills fresh mappings adds one for every 4 KiB it moves."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


class _ReadAhead:
    """The host arrays of ``shards`` (single-device ``jax.Array``s), in
    order: each item is an iterator over the ndarrays whose bytes, in
    order, are that shard's.

    The runtime gives every transfer a new host buffer and fills it
    with one thread. A whole shard at a time that reads 1.4-1.8 GB/s on
    a v5e, and 2.4 GB/s with all of them in flight: the buffers are
    fresh mappings whose pages fault in as they are filled. Many
    transfers of a few MiB in flight, each dropped once its bytes are
    in the segment, read 3.6-7.3 GB/s (PERF.md, PR 25), provided the
    allocator serves them from the heap: its threshold for that moves
    between 128 KiB and 32 MiB with what the process frees, so the
    engine pins it (``_keep_transfer_buffers_on_the_heap``; PERF.md,
    PR 47). So a shard above ``_PIECE_BYTES`` is first laid out
    linearly on the device and cut into pieces, and shards are started
    until ``_AHEAD_BYTES`` are on their way; taking a shard's arrays
    starts the ones behind it. ``in_flight`` counts the transfers
    started before the first wait.
    """

    def __init__(self, shards):
        self._todo = iter(shards)
        self._started = collections.deque()  # (nbytes, its pieces)
        self._ahead = 0  # bytes started and not yet taken
        self._start_more()
        self.in_flight = sum(len(p) for _, p in self._started)

    def _start_more(self) -> None:
        while self._ahead < _AHEAD_BYTES:
            shard = next(self._todo, None)
            if shard is None:
                return
            pieces = (shard,) if shard.nbytes <= _PIECE_BYTES else (
                _linear_pieces()(
                    shard, _PIECE_BYTES // shard.dtype.itemsize))
            for piece in pieces:
                piece.copy_to_host_async()
            self._started.append((shard.nbytes, pieces))
            self._ahead += shard.nbytes

    def __iter__(self) -> Iterator[Iterator[np.ndarray]]:
        while self._started:
            nbytes, pieces = self._started.popleft()
            yield (np.asarray(piece) for piece in pieces)
            self._ahead -= nbytes
            self._start_more()


class CheckpointEngine:
    """Stages sharded jax state into shm; loads committed checkpoints.

    One engine per training process. ``local_rank`` selects the shm
    segment shared with the host agent; ``global_rank``/``world_size``
    name this process's shard files in storage.
    """

    def __init__(
        self,
        checkpoint_dir: str,
        local_rank: int = 0,
        global_rank: Optional[int] = None,
        world_size: Optional[int] = None,
        use_agent: bool = True,
        storage=None,
    ):
        import jax

        from dlrover_tpu.common.storage import get_storage

        self.checkpoint_dir = checkpoint_dir
        self.storage = storage or get_storage()
        self.local_rank = local_rank
        self.global_rank = (jax.process_index()
                            if global_rank is None else global_rank)
        self.world_size = (jax.process_count()
                           if world_size is None else world_size)
        self._shm = SharedMemoryHandler(local_rank)
        self._use_agent = use_agent
        if use_agent:
            self._lock = SharedLock(f"ckpt_{local_rank}")
            self._events = SharedQueue(CKPT_EVENT_QUEUE)
            self._status = SharedDict(CKPT_STATUS_DICT)
        else:
            self._lock = None
            self._events = None
            self._status = None
        self._cached_step = -1
        _keep_transfer_buffers_on_the_heap()

    # -- save ------------------------------------------------------------

    def _plan(self, state) -> Tuple[List[TensorEntry], list, int]:
        """This process's primary shards, laid out in the segment:
        (entries, each entry's single-device array, leaves)."""
        import jax

        plans = []
        shards = []
        named = flatten_named(state)
        for name, leaf in named:
            if not isinstance(leaf, jax.Array):
                leaf = jax.numpy.asarray(leaf)
            gshape = leaf.shape
            seen_index = set()
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue
                index = tuple(
                    (sl.start or 0,
                     sl.stop if sl.stop is not None else gshape[d])
                    for d, sl in enumerate(shard.index)
                )
                # Several addressable devices can hold replica 0 of the
                # same logical shard under nested replication; write
                # each logical slice once.
                if index in seen_index:
                    continue
                seen_index.add(index)
                plans.append((name, str(leaf.dtype), gshape, index,
                              shard.data.nbytes))
                shards.append(shard.data)
        entries, _ = plan_entries(plans)
        return entries, shards, len(named)

    def _stage(self, step: int, state, extra: dict) -> None:
        """device→shm: start the transfers of the planned shards
        (``_ReadAhead``), then write each array into the segment as it
        arrives, in plan order. Returns once the last byte is in the
        segment and the segment is published."""
        entries, shards, leaves = self._plan(state)
        nbytes = sum(e.nbytes for e in entries)
        with obs.span("ckpt.d2h", bytes=nbytes, leaves=leaves) as d2h:
            t0 = time.monotonic()
            faults = _minor_faults()
            arrivals = _ReadAhead(shards)
            d2h.set(in_flight=arrivals.in_flight)
            with obs.span("ckpt.shm_copy", bytes=nbytes) as copy:
                copy.set(write_s=round(
                    self._shm.save(step, entries, arrivals, extra), 6))
            d2h.set(
                gbps=round(
                    nbytes / 1e9 / max(time.monotonic() - t0, 1e-9), 3),
                minor_faults=_minor_faults() - faults)

    def save_to_memory(self, step: int, state,
                       extra: Optional[dict] = None) -> bool:
        """Stage ``state`` into shm. Non-blocking wrt storage; skips
        (returns False) if the agent is mid-persist on this segment."""
        extra = dict(extra or {})
        extra["_global_rank"] = self.global_rank
        extra["_world_size"] = self.world_size
        # Stamp the trainer's authoritative dir into the staged
        # metadata: the agent flushing a memory-only checkpoint before
        # a restart must persist where the resumed trainer will look,
        # even if it never saw a save_to_storage event.
        extra["_checkpoint_dir"] = self.checkpoint_dir
        # Trylock *before* the device→host copy so a busy agent costs
        # nothing — staging multi-GB state only to drop it would stall
        # the train loop for seconds.
        if self._lock is not None and not self._lock.acquire(
                blocking=False):
            logger.warning(
                "step %s: shm busy (agent persisting); skip staging",
                step)
            _CKPT_OPS.inc(op="save_memory", result="skipped")
            obs.event("ckpt.save_skipped", step=step, reason="shm_busy")
            return False
        t0 = time.monotonic()
        try:
            with obs.span("ckpt.save_memory", step=step):
                self._stage(step, state, extra)
            self._cached_step = step
        except Exception:
            # Staging failures must be countable from /metrics, not
            # only visible as exceptions in one process's stderr.
            _CKPT_OPS.inc(op="save_memory", result="error")
            raise
        finally:
            if self._lock is not None:
                self._lock.release()
        _CKPT_STAGE_SECONDS.observe(time.monotonic() - t0)
        _CKPT_OPS.inc(op="save_memory", result="ok")
        return True

    def save_to_storage(self, step: int, state,
                        extra: Optional[dict] = None) -> bool:
        """Stage into shm then ask the agent to persist asynchronously."""
        if not self.save_to_memory(step, state, extra):
            return False
        if self._events is not None:
            # The agent-hosted saver learns the checkpoint dir from the
            # event: the agent starts before any trainer chose a dir.
            with obs.span("ckpt.notify_agent", step=step):
                self._events.put(
                    {
                        "type": "save",
                        "step": step,
                        "dir": self.checkpoint_dir,
                    }
                )
        _CKPT_OPS.inc(op="persist_request", result="ok")
        obs.event("ckpt.persist_requested", step=step)
        return True

    def wait_persisted(self, step: int, timeout: float = 60.0) -> bool:
        """Block until the agent reports ``step`` committed (tests,
        graceful shutdown)."""
        if self._status is None:
            return False
        deadline = time.time() + timeout
        while time.time() < deadline:
            if int(self._status.get("latest_persisted_step", -1)) >= step:
                return True
            time.sleep(0.05)
        return False

    # -- load ------------------------------------------------------------

    def latest_step(self) -> int:
        """Latest committed step in storage, or -1."""
        path = f"{self.checkpoint_dir.rstrip('/')}/{TRACKER_FILE}"
        if not self.storage.exists(path):
            return -1
        txt = self.storage.read_bytes(path).decode().strip()
        return int(txt) if txt else -1

    def load_flat(self, step: Optional[int] = None
                  ) -> Optional[Tuple[int, Dict[str, np.ndarray], dict]]:
        """Load {leaf-name: global ndarray} for the latest (or given)
        committed step, merging every rank's shard files."""
        if step is None:
            step = self.latest_step()
        if step < 0:
            return None
        sdir = step_dir(self.checkpoint_dir, step)
        entries: List[TensorEntry] = []
        payloads: List[bytes] = []
        extra: dict = {}
        offset = 0
        found = False
        for fname in self.storage.listdir(sdir):
            if not fname.endswith(".ckpt"):
                continue
            found = True
            shard_step, shard_entries, shard_extra, payload = (
                unpack_shard_file(
                    self.storage.read_bytes(f"{sdir}/{fname}")))
            if shard_step != step:
                raise ValueError(
                    f"shard {fname} holds step {shard_step}, dir says "
                    f"{step}: corrupt checkpoint")
            for e in shard_entries:
                e.offset += offset
                entries.append(e)
            payloads.append(payload)
            offset += len(payload)
            for k, v in shard_extra.items():
                if not k.startswith("_"):
                    extra[k] = v
        if not found:
            return None
        flat = ckpt_shm.assemble_global(entries, b"".join(payloads))
        return step, flat, extra

    def read_shard_metas(self, step: Optional[int] = None):
        """Read ONLY the meta headers of every shard file of a
        committed step — no payload bytes touched. Returns
        (step, index, extra) where ``index`` maps leaf name to a list
        of (path, payload_base, TensorEntry)."""
        if step is None:
            step = self.latest_step()
        if step < 0:
            return None
        sdir = step_dir(self.checkpoint_dir, step)
        index: Dict[str, List[Tuple[str, int, TensorEntry]]] = {}
        extra: dict = {}
        found = False
        for fname in self.storage.listdir(sdir):
            if not fname.endswith(".ckpt"):
                continue
            found = True
            path = f"{sdir}/{fname}"
            meta_len = int.from_bytes(
                self.storage.read_range(path, 0, 8), "little")
            shard_step, shard_entries, shard_extra = (
                ckpt_shm.unpack_meta(
                    self.storage.read_range(path, 8, meta_len)))
            if shard_step != step:
                raise ValueError(
                    f"shard {fname} holds step {shard_step}, dir says "
                    f"{step}: corrupt checkpoint")
            base = 8 + meta_len
            for e in shard_entries:
                index.setdefault(e.name, []).append((path, base, e))
            for k, v in shard_extra.items():
                if not k.startswith("_"):
                    extra[k] = v
        if not found:
            return None
        return step, index, extra

    def _read_slice(self, sources, gshape, dtype_name, target_index
                    ) -> np.ndarray:
        """Assemble the sub-array ``target_index`` (tuple of slices
        into the global array) by fetching ONLY the byte ranges of
        source entries that overlap it. When the overlap is a leading-
        axis band of the entry (the common FSDP/data row sharding),
        only that contiguous band's bytes are read — not the entry."""
        raw = ckpt_shm._np_view(dtype_name)
        np_dtype = (np.dtype(raw) if raw is not None
                    else np.dtype(dtype_name))
        tgt = tuple(
            (sl.start or 0,
             sl.stop if sl.stop is not None else gshape[d])
            for d, sl in enumerate(target_index))
        shape = tuple(stop - start for start, stop in tgt)
        out = np.empty(shape, np_dtype)
        filled = 0
        for path, base, e in sources:
            box = tuple(
                (max(ts, es), min(te, ee))
                for (ts, te), (es, ee) in zip(tgt, e.index))
            if any(start >= stop for start, stop in box):
                continue  # no overlap: its bytes are never read
            lshape = e.local_shape
            local_box = tuple(
                (start - es, stop - es)
                for (start, stop), (es, _) in zip(box, e.index))
            full_tail = all(
                lo == 0 and hi == dim
                for (lo, hi), dim in zip(local_box[1:], lshape[1:]))
            if full_tail and lshape:
                # contiguous row band: read rows [lo0, hi0) only
                lo0, hi0 = local_box[0] if local_box else (0, 1)
                row_bytes = (int(np.prod(lshape[1:], dtype=np.int64))
                             * np_dtype.itemsize)
                data = self.storage.read_range(
                    path,
                    base + e.offset + lo0 * row_bytes,
                    (hi0 - lo0) * row_bytes)
                src = np.frombuffer(data, np_dtype).reshape(
                    (hi0 - lo0,) + lshape[1:])
                src_sl = (slice(None),) + tuple(
                    slice(lo, hi) for lo, hi in local_box[1:])
            else:
                data = self.storage.read_range(
                    path, base + e.offset, e.nbytes)
                src = np.frombuffer(data, np_dtype).reshape(lshape)
                src_sl = tuple(
                    slice(lo, hi) for lo, hi in local_box)
            dst_sl = tuple(
                slice(start - ts, stop - ts)
                for (start, stop), (ts, _) in zip(box, tgt))
            out[dst_sl] = src[src_sl]
            filled += int(np.prod([b - a for a, b in box]))
        if filled < int(np.prod(shape)):
            raise ValueError(
                "checkpoint shards do not cover the requested slice "
                f"(got {filled} of {int(np.prod(shape))} elements)")
        return ckpt_shm.np_from_raw(out, dtype_name)

    def load_streaming(self, like, shardings,
                       step: Optional[int] = None):
        """Streaming reshard-on-load: each host reads only the byte ranges
        its own device shards need (O(local shards) host RAM and IO,
        not O(model)) — the fix for whole-checkpoint restore; parity:
        atorch/utils/fsdp_save_util.py streaming restore + TP reshard.

        Returns (step, state, extra) or None.
        """
        import jax

        named = flatten_named(like)
        like_def = jax.tree_util.tree_structure(like)
        sharding_leaves = jax.tree_util.tree_leaves(shardings)
        # Every byte is read before the first array is placed, so the
        # two halves of a restore can be told apart (the host then
        # holds this process's shards at once, as a save does).
        with obs.span("ckpt.restore_read", source="disk") as span:
            res = self.read_shard_metas(step)
            if res is None:
                return None
            found_step, index, extra = res
            shard_def = jax.tree_util.tree_structure(shardings)
            if like_def != shard_def:
                raise ValueError(
                    f"shardings tree structure {shard_def} does not "
                    f"match `like` tree structure {like_def}")
            # Fail on missing leaves BEFORE streaming gigabytes of the
            # present ones.
            missing = [n for n, _ in named if n not in index]
            if missing:
                raise KeyError(
                    f"checkpoint step {found_step} missing leaves: "
                    f"{missing[:5]}{'...' if len(missing) > 5 else ''}")
            nbytes = 0
            readers = []
            for (name, _), sharding in zip(named, sharding_leaves):
                sources = index[name]
                gshape = sources[0][2].global_shape
                dtype_name = sources[0][2].dtype
                # Replicated device shards share an index: assemble
                # each UNIQUE slice once, not once per device.
                slice_cache: Dict[Tuple, np.ndarray] = {}

                def read_cached(idx, s=sources, g=gshape, d=dtype_name,
                                cache=slice_cache):
                    key = tuple(
                        (sl.start, sl.stop, sl.step) for sl in idx)
                    if key not in cache:
                        cache[key] = self._read_slice(s, g, d, idx)
                    return cache[key]

                for idx in sharding.addressable_devices_indices_map(
                        gshape).values():
                    read_cached(idx)
                nbytes += sum(a.nbytes for a in slice_cache.values())
                readers.append((gshape, read_cached))
            span.set(bytes=nbytes, step=found_step)
        TrainingMonitor.mark_phase("restore_read_done")
        with obs.span("ckpt.restore_put"):
            leaves = []
            for (_, leaf), sharding, (gshape, read_cached) in zip(
                    named, sharding_leaves, readers):
                arr = jax.make_array_from_callback(
                    gshape, sharding, read_cached,
                )
                jdtype = getattr(leaf, "dtype", None)
                if jdtype is not None and arr.dtype != jdtype:
                    arr = arr.astype(jdtype)
                leaves.append(arr)
            state = jax.tree_util.tree_unflatten(like_def, leaves)
        return found_step, state, extra

    def load(self, like, shardings=None,
             step: Optional[int] = None):
        """Restore a pytree shaped like ``like`` (arrays or
        ShapeDtypeStructs). If ``shardings`` (matching pytree of
        NamedSharding) is given, the restore STREAMS: each host fetches
        only the shard byte-ranges its devices need (see
        :meth:`load_streaming`). Without shardings the full state is
        assembled host-side (load_flat).

        Returns (step, state, extra) or None when no checkpoint exists.
        """
        t0 = time.monotonic()
        with obs.span("ckpt.restore"):
            res = self._load(like, shardings, step)
        if res is None:
            _CKPT_OPS.inc(op="restore", result="none")
        else:
            _CKPT_RESTORE_SECONDS.observe(time.monotonic() - t0)
            _CKPT_OPS.inc(op="restore", result="ok")
        return res

    def _load(self, like, shardings=None,
              step: Optional[int] = None):
        import jax

        # Streaming needs real ranged reads; on a backend whose
        # read_range is the whole-object fallback, each range request
        # would re-download the file — assemble-then-reshard instead.
        if shardings is not None and self.storage.supports_range():
            return self.load_streaming(like, shardings, step)
        with obs.span("ckpt.restore_read", source="disk") as span:
            res = self.load_flat(step)
            if res is None:
                return None
            found_step, flat, extra = res
            span.set(
                bytes=sum(a.nbytes for a in flat.values()),
                step=found_step,
            )
        TrainingMonitor.mark_phase("restore_read_done")
        named = flatten_named(like)
        missing = [name for name, _ in named if name not in flat]
        if missing:
            raise KeyError(
                f"checkpoint step {found_step} missing leaves: "
                f"{missing[:5]}{'...' if len(missing) > 5 else ''}")
        treedef = jax.tree_util.tree_structure(like)
        state = jax.tree_util.tree_unflatten(
            treedef, [flat[name] for name, _ in named]
        )
        with obs.span("ckpt.restore_put"):
            if shardings is not None:
                # Match load_streaming: cast to `like`'s dtype so the
                # two backends produce identical state trees.
                def put(x, l, s):
                    want = getattr(l, "dtype", None)
                    if want is not None and x.dtype != want:
                        x = x.astype(want)
                    return jax.device_put(x, s)

                state = jax.tree.map(put, state, like, shardings)
            else:
                state = jax.tree.map(jax.numpy.asarray, state)
        return found_step, state, extra

    def close(self) -> None:
        self._shm.close()
        for h in (self._lock, self._events, self._status):
            if h is not None:
                h.close()
