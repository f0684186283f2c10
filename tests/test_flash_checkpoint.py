"""Flash Checkpoint tests.

Modeled on the reference's test strategy (dlrover/python/tests/
test_ckpt_saver.py + trainer checkpoint tests): real shm + real saver
thread in one process, sharded arrays on the virtual 8-device CPU mesh,
reshard-on-load across different mesh shapes.
"""

import ctypes
import importlib.util
import os
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu import obs
from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.common import ckpt_shm
from dlrover_tpu.trainer.flash_checkpoint import engine as engine_mod
from dlrover_tpu.trainer.flash_checkpoint.engine import CheckpointEngine


@pytest.fixture(autouse=True)
def _isolated_job(monkeypatch, tmp_path):
    """Unique job name per test so shm segments/sockets don't collide."""
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", f"t{uuid.uuid4().hex[:8]}")
    yield


@pytest.fixture()
def saver(tmp_path):
    s = AsyncCheckpointSaver(
        checkpoint_dir=str(tmp_path / "ckpt"),
        local_shard_num=1,
        global_shard_num=1,
        commit_timeout=20.0,
    )
    s.start()
    yield s
    s.close()
    for shm in s._shms:
        shm.unlink()


def _mesh(shape, names):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _state(mesh):
    w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    b = jnp.ones((8,), jnp.bfloat16)
    sharded_w = jax.device_put(
        w, NamedSharding(mesh, P("data", None)))
    return {"w": sharded_w, "inner": {"b": b, "step_scale": jnp.float32(2.0)}}


class TestShmFormat:
    def test_roundtrip(self):
        arrs = [
            ("a/b", np.arange(12, dtype=np.float32).reshape(3, 4)),
            ("c", np.ones((5,), np.int32)),
        ]
        plans = [
            (name, str(a.dtype), a.shape,
             [(0, s) for s in a.shape], a.nbytes)
            for name, a in arrs
        ]
        entries, total = ckpt_shm.plan_entries(plans)
        assert entries[1].offset % 128 == 0
        handler = ckpt_shm.SharedMemoryHandler(0)
        try:
            handler.save(7, entries, [a for _, a in arrs], {"k": "v"})
            step, got_entries, extra, payload = handler.load()
            assert step == 7 and extra["k"] == "v"
            flat = ckpt_shm.assemble_global(got_entries, payload)
            np.testing.assert_array_equal(flat["a/b"], arrs[0][1])
            np.testing.assert_array_equal(flat["c"], arrs[1][1])
        finally:
            handler.unlink()
            handler.close()

    def test_bf16_raw_staging(self):
        import ml_dtypes

        a = np.arange(8, dtype=ml_dtypes.bfloat16)
        raw = a.view(np.uint16)
        plans = [("x", "bfloat16", a.shape, [(0, 8)], raw.nbytes)]
        entries, _ = ckpt_shm.plan_entries(plans)
        handler = ckpt_shm.SharedMemoryHandler(0)
        try:
            handler.save(1, entries, [raw])
            _, got, _, payload = handler.load()
            flat = ckpt_shm.assemble_global(got, payload)
            assert flat["x"].dtype == ml_dtypes.bfloat16
            np.testing.assert_array_equal(flat["x"], a)
        finally:
            handler.unlink()
            handler.close()


    @pytest.mark.parametrize("pieces", [
        pytest.param(lambda a: a, id="one-array"),
        pytest.param(lambda a: [a[:1], a[1:]], id="two-pieces"),
        pytest.param(lambda a: iter(np.split(a.reshape(-1), 4)),
                     id="an-iterator-of-four"),
    ])
    def test_an_entry_is_its_pieces_in_order(self, pieces):
        a = np.arange(24, dtype=np.float32).reshape(4, 6)
        b = np.arange(5, dtype=np.int32)
        plans = [("a", "float32", a.shape, [(0, 4), (0, 6)], a.nbytes),
                 ("b", "int32", b.shape, [(0, 5)], b.nbytes)]
        entries, _ = ckpt_shm.plan_entries(plans)
        handler = ckpt_shm.SharedMemoryHandler(0)
        try:
            copy_s = handler.save(2, entries, iter([pieces(a), b]))
            assert copy_s >= 0.0
            _, got, _, payload = handler.load()
            flat = ckpt_shm.assemble_global(got, payload)
            np.testing.assert_array_equal(flat["a"], a)
            np.testing.assert_array_equal(flat["b"], b)
        finally:
            handler.unlink()
            handler.close()

    @pytest.mark.parametrize("arrivals", [
        pytest.param(lambda a, b: [a[:3], b], id="too-few-bytes"),
        pytest.param(lambda a, b: [[a, a[:1]], b], id="too-many-bytes"),
        pytest.param(lambda a, b: [a], id="an-entry-never-arrives"),
        pytest.param(lambda a, b: [a, b, b], id="one-arrival-too-many"),
    ])
    def test_a_save_that_does_not_fit_its_plan_leaves_no_state(
            self, arrivals):
        a = np.arange(24, dtype=np.float32).reshape(4, 6)
        b = np.arange(5, dtype=np.int32)
        plans = [("a", "float32", a.shape, [(0, 4), (0, 6)], a.nbytes),
                 ("b", "int32", b.shape, [(0, 5)], b.nbytes)]
        entries, _ = ckpt_shm.plan_entries(plans)
        handler = ckpt_shm.SharedMemoryHandler(0)
        try:
            handler.save(1, entries, [a, b])
            assert handler.load()[0] == 1
            with pytest.raises(ValueError):
                handler.save(2, entries, arrivals(a, b))
            assert handler.load() is None
        finally:
            handler.unlink()
            handler.close()


class _FakeShard:
    """Stands for a single-device array: says when its transfer was
    started and when its value was taken."""

    def __init__(self, log, i, nbytes):
        self.log, self.i, self.nbytes = log, i, nbytes

    def copy_to_host_async(self):
        self.log.append(("start", self.i))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("take", self.i))
        return np.full(self.nbytes, self.i, np.uint8)


class TestReadAhead:
    @pytest.mark.parametrize("n, nbytes, ahead", [
        pytest.param(6, 64, None, id="all-within-the-window"),
        pytest.param(7, 100, 250, id="a-window-of-three"),
        pytest.param(4, 100, 1, id="a-window-of-one"),
    ])
    def test_transfers_are_started_before_any_is_waited_on(
            self, monkeypatch, n, nbytes, ahead):
        if ahead is not None:
            monkeypatch.setattr(engine_mod, "_AHEAD_BYTES", ahead)
        window = n if ahead is None else -(-ahead // nbytes)
        log = []
        read = engine_mod._ReadAhead(
            [_FakeShard(log, i, nbytes) for i in range(n)])
        # Before the first wait: the whole window is on its way.
        assert log == [("start", i) for i in range(window)]
        assert read.in_flight == window
        for i, pieces in enumerate(read):
            (host,) = pieces
            assert host.tobytes() == bytes([i]) * nbytes
            started = [k for what, k in log if what == "start"]
            taken = [k for what, k in log if what == "take"]
            # In plan order, each started before it is taken, and
            # never more than the window ahead of the one being taken.
            assert taken == list(range(i + 1))
            assert started == list(range(len(started)))
            assert i < len(started) <= i + window
        assert [k for what, k in log if what == "start"] == list(range(n))

    def test_a_shard_above_the_piece_size_arrives_in_linear_pieces(
            self, monkeypatch):
        monkeypatch.setattr(engine_mod, "_PIECE_BYTES", 4096)
        big = jnp.arange(48 * 100, dtype=jnp.bfloat16).reshape(48, 100)
        small = jnp.arange(7, dtype=jnp.int32)
        read = engine_mod._ReadAhead([big, small])
        assert read.in_flight == 3 + 1
        got_big, got_small = ([np.asarray(p) for p in pieces]
                              for pieces in read)
        assert [p.shape for p in got_big] == [(2048,), (2048,), (704,)]
        assert (b"".join(p.tobytes() for p in got_big)
                == np.asarray(big).tobytes())
        assert got_small[0].tobytes() == np.asarray(small).tobytes()


_PIN_MMAP = (engine_mod._M_MMAP_THRESHOLD, engine_mod._MMAP_THRESHOLD)
_PIN_TRIM = (engine_mod._M_TRIM_THRESHOLD, engine_mod._TRIM_THRESHOLD)


class TestHeapPin:
    """The engine pins the C allocator's mmap and trim thresholds, so
    that a save's transfer buffers are heap memory whatever the process
    freed before: once a process, when an engine is built."""

    @pytest.mark.parametrize("env, mallopt, calls, pinned, ok, libc", [
        pytest.param({}, "real", [_PIN_MMAP, _PIN_TRIM],
                     (_PIN_MMAP[1], _PIN_TRIM[1]), True, "glibc",
                     id="two-engines-pin-once"),
        pytest.param({"MALLOC_MMAP_THRESHOLD_": "131072"}, "real",
                     [_PIN_TRIM], (None, _PIN_TRIM[1]), True, "glibc",
                     id="the-users-mmap-threshold-is-left-alone"),
        pytest.param({"MALLOC_TRIM_THRESHOLD_": "131072"}, "real",
                     [_PIN_MMAP], (_PIN_MMAP[1], None), True, "glibc",
                     id="the-users-trim-threshold-is-left-alone"),
        pytest.param({}, "missing", [], (None, None), False, "other",
                     id="no-mallopt-and-the-save-goes-on"),
        pytest.param({}, "refuses", [_PIN_MMAP, _PIN_TRIM], (None, None),
                     False, "glibc",
                     id="mallopt-returns-0-and-the-save-goes-on"),
        pytest.param(None, "real", [], None, None, None,
                     id="importing-the-module-pins-nothing"),
    ])
    def test_the_thresholds_are_pinned_once_a_process(
            self, saver, monkeypatch, tmp_path, env, mallopt, calls,
            pinned, ok, libc):
        real = engine_mod._glibc_mallopt()
        assert real is not None, "these machines run glibc"
        made = []

        def counted(param, value):
            made.append((param, value))
            return real(param, value) if mallopt == "real" else 0

        pin = engine_mod._keep_transfer_buffers_on_the_heap
        pin.cache_clear()
        monkeypatch.setattr(
            engine_mod, "_glibc_mallopt",
            lambda: None if mallopt == "missing" else counted)
        for name in ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_"):
            monkeypatch.delenv(name, raising=False)
        for name, value in (env or {}).items():
            monkeypatch.setenv(name, value)
        tracer = obs.configure_tracer()
        engines = []
        try:
            if env is None:
                # A fresh copy of the module, run as an import runs it:
                # the C library is not even looked up.
                loaded = []
                monkeypatch.setattr(ctypes, "CDLL", loaded.append)
                spec = importlib.util.spec_from_file_location(
                    "engine_imported_alone", engine_mod.__file__)
                alone = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(alone)
                alone_pin = alone._keep_transfer_buffers_on_the_heap
                assert alone_pin.cache_info().currsize == 0 and not loaded
            else:
                engines = [
                    CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
                    for _ in range(2)]
                state = _state(_mesh((4, 2), ("data", "tensor")))
                assert engines[0].save_to_storage(4, state)
                assert engines[0].wait_persisted(4, timeout=20)
                step, restored, _ = engines[1].load(state)
                assert step == 4
                for got, want in zip(jax.tree.leaves(restored),
                                     jax.tree.leaves(state)):
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(want))
            events = [e for e in tracer.events()
                      if e["name"] == "ckpt.heap_pinned"]
        finally:
            obs.disable_tracer()
            for engine in engines:
                engine.close()
            # The next engine of this process pins for itself again.
            pin.cache_clear()
        assert made == calls
        if env is None:
            assert events == []
            return
        (event,) = events
        assert (event["mmap_threshold"], event["trim_threshold"]) == pinned
        assert event["ok"] is ok and event["libc"] == libc

    def test_the_d2h_span_counts_the_saves_minor_faults(self, tmp_path):
        state = _state(_mesh((8,), ("data",)))
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=False)
        tracer = obs.configure_tracer()
        try:
            assert engine.save_to_memory(1, state)
            (d2h,) = [e for e in tracer.events()
                      if e["name"] == "ckpt.d2h"]
        finally:
            obs.disable_tracer()
            engine._shm.unlink()
            engine.close()
        assert type(d2h["minor_faults"]) is int
        assert d2h["minor_faults"] >= 0


class TestEngineSaverEndToEnd:
    def test_save_and_commit(self, saver, tmp_path):
        mesh = _mesh((4, 2), ("data", "tensor"))
        state = _state(mesh)
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
        try:
            assert engine.save_to_storage(10, state, {"lr": 0.1})
            assert engine.wait_persisted(10, timeout=20)
            assert engine.latest_step() == 10
            step, flat, extra = engine.load_flat()
            assert step == 10 and extra["lr"] == 0.1
            np.testing.assert_array_equal(
                flat["w"], np.arange(64, dtype=np.float32).reshape(8, 8))
            np.testing.assert_array_equal(
                np.asarray(flat["inner/b"], np.float32), np.ones(8))
        finally:
            engine.close()

    def test_memory_only_then_flush(self, saver, tmp_path):
        """save_to_memory leaves storage untouched; the agent's
        failure-path flush (save_shm_to_storage) persists it."""
        mesh = _mesh((8,), ("data",))
        state = _state(mesh)
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
        try:
            assert engine.save_to_memory(5, state)
            assert engine.latest_step() == -1
            assert saver.save_shm_to_storage()
            assert engine.latest_step() == 5
        finally:
            engine.close()

    def test_reshard_on_load(self, saver, tmp_path):
        """Save on a (4,2) data×tensor mesh, restore onto (2,4)."""
        mesh_a = _mesh((4, 2), ("data", "tensor"))
        w = jnp.arange(256, dtype=jnp.float32).reshape(16, 16)
        sharded = jax.device_put(
            w, NamedSharding(mesh_a, P("data", "tensor")))
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
        try:
            assert engine.save_to_storage(3, {"w": sharded})
            assert engine.wait_persisted(3, timeout=20)

            mesh_b = _mesh((2, 4), ("data", "tensor"))
            target = NamedSharding(mesh_b, P("tensor", "data"))
            like = {"w": jax.ShapeDtypeStruct((16, 16), jnp.float32)}
            step, restored, _ = engine.load(
                like, shardings={"w": target})
            assert step == 3
            np.testing.assert_array_equal(np.asarray(restored["w"]), w)
            assert restored["w"].sharding == target
        finally:
            engine.close()

    def test_newer_save_wins(self, saver, tmp_path):
        mesh = _mesh((8,), ("data",))
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
        try:
            for step in (1, 2):
                state = {"x": jax.device_put(
                    jnp.full((8,), step, jnp.float32),
                    NamedSharding(mesh, P("data")))}
                assert engine.save_to_storage(step, state)
                assert engine.wait_persisted(step, timeout=20)
            assert engine.latest_step() == 2
            _, flat, _ = engine.load_flat()
            np.testing.assert_array_equal(flat["x"], np.full(8, 2.0))
        finally:
            engine.close()


class TestStagedBytes:
    """What a save leaves in the segment, against one ``np.asarray``
    of each planned shard taken here."""

    def _mixed_state(self):
        mesh = _mesh((4,), ("data",))
        rows = NamedSharding(mesh, P("data", None))
        everywhere = NamedSharding(mesh, P())
        key = jax.random.PRNGKey(0)
        return {
            "w": jax.device_put(
                jax.random.normal(key, (64, 128), jnp.float32), rows),
            "h": jax.device_put(
                jax.random.normal(key, (48, 100)).astype(jnp.bfloat16),
                everywhere),
            "inner": {
                "v": jax.device_put(
                    jnp.arange(4 * 1500, dtype=jnp.bfloat16
                               ).reshape(4, 1500), rows),
                "b": jax.device_put(
                    jnp.arange(100, dtype=jnp.float32), everywhere),
                "count": jnp.int32(7),
            },
        }

    @pytest.mark.parametrize("piece_bytes, ahead_bytes", [
        pytest.param(None, None, id="every-shard-as-it-lies"),
        pytest.param(4096, None, id="large-shards-in-pieces"),
        pytest.param(4096, 1, id="in-pieces-one-shard-ahead"),
        pytest.param(1000, 10000, id="odd-pieces-a-small-window"),
    ])
    def test_the_segment_holds_each_shards_bytes(
            self, monkeypatch, tmp_path, piece_bytes, ahead_bytes):
        if piece_bytes is not None:
            monkeypatch.setattr(engine_mod, "_PIECE_BYTES", piece_bytes)
        if ahead_bytes is not None:
            monkeypatch.setattr(engine_mod, "_AHEAD_BYTES", ahead_bytes)
        state = self._mixed_state()
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=False)
        try:
            entries, shards, leaves = engine._plan(state)
            assert leaves == 5
            # Sharded leaves: a shard a device; replicated: one.
            assert [e.name for e in entries] == (
                ["h"] + ["inner/b", "inner/count"] + ["inner/v"] * 4
                + ["w"] * 4)
            assert engine.save_to_memory(9, state, {"k": 1})
            step, got, extra, payload = engine._shm.load()
            assert step == 9 and extra["k"] == 1
            assert ([e.to_dict() for e in got]
                    == [e.to_dict() for e in entries])
            for e, shard in zip(entries, shards):
                want = np.asarray(shard).tobytes()
                assert len(want) == e.nbytes
                assert payload[e.offset:e.offset + e.nbytes] == want, (
                    e.name, e.index)
            flat = ckpt_shm.assemble_global(got, payload)
            for name, leaf in engine_mod.flatten_named(state):
                assert flat[name].dtype == leaf.dtype
                np.testing.assert_array_equal(flat[name], np.asarray(leaf))
        finally:
            engine._shm.unlink()
            engine.close()

    @pytest.mark.parametrize("fails_at", [0, 2, 4])
    def test_a_transfer_that_fails_leaves_no_state(
            self, saver, monkeypatch, tmp_path, fails_at):
        state = self._mixed_state()
        real_iter = engine_mod._ReadAhead.__iter__

        def breaks(self):
            for i, pieces in enumerate(real_iter(self)):
                if i == fails_at:
                    raise RuntimeError("transfer failed")
                yield pieces

        errors = engine_mod._CKPT_OPS.value(
            op="save_memory", result="error")
        engine = CheckpointEngine(str(tmp_path / "ckpt"), use_agent=True)
        try:
            assert engine.save_to_memory(1, state)
            assert engine._shm.load()[0] == 1
            monkeypatch.setattr(engine_mod._ReadAhead, "__iter__", breaks)
            with pytest.raises(RuntimeError, match="transfer failed"):
                engine.save_to_memory(2, state)
            # No state, rather than step 1's bytes under step 2's
            # name; the lock is free; the failure is counted once.
            assert engine._shm.load() is None
            assert engine._lock.acquire(blocking=False)
            engine._lock.release()
            assert engine_mod._CKPT_OPS.value(
                op="save_memory", result="error") == errors + 1
            monkeypatch.setattr(
                engine_mod._ReadAhead, "__iter__", real_iter)
            assert engine.save_to_memory(3, state)
            assert engine._shm.load()[0] == 3
        finally:
            engine.close()


class CountingStorage:
    """PosixStorage wrapper that accounts every byte read."""

    def __init__(self):
        from dlrover_tpu.common.storage import PosixStorage

        self._s = PosixStorage()
        self.full_read_paths = []
        self.range_bytes = 0

    def read_bytes(self, path):
        self.full_read_paths.append(path)
        return self._s.read_bytes(path)

    def read_range(self, path, offset, length):
        self.range_bytes += length
        return self._s.read_range(path, offset, length)

    def __getattr__(self, name):
        return getattr(self._s, name)


def _craft_checkpoint(tmp_path, step=5):
    """Hand-craft a 2-host checkpoint: rank0 holds rows 0:8 of ``w``
    plus a big ``junk`` leaf, rank1 holds rows 8:16 of ``w``. Returns
    (ckpt_dir, w, junk_nbytes, total_payload_bytes)."""
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        TRACKER_FILE,
        pack_shard_file,
    )

    ckpt_dir = str(tmp_path / "crafted")
    sdir = f"{ckpt_dir}/{step}"
    os.makedirs(sdir, exist_ok=True)
    w = np.arange(256, dtype=np.float32).reshape(16, 16)
    junk = np.ones((64, 64), np.float32)  # 16KB nobody asks for

    total = 0
    for rank, (rows, extras) in enumerate(
        [((0, 8), [("junk", junk)]), ((8, 16), [])]
    ):
        arrays = [("w", w[rows[0]:rows[1]],
                   ((rows[0], rows[1]), (0, 16)), (16, 16))]
        for name, arr in extras:
            arrays.append(
                (name, arr,
                 tuple((0, s) for s in arr.shape), arr.shape)
            )
        plans = [
            (name, str(arr.dtype), gshape, index, arr.nbytes)
            for name, arr, index, gshape in arrays
        ]
        entries, size = ckpt_shm.plan_entries(plans)
        payload = bytearray(size)
        for e, (_, arr, _, _) in zip(entries, arrays):
            payload[e.offset:e.offset + e.nbytes] = arr.tobytes()
        data = pack_shard_file(step, entries, {}, bytes(payload))
        with open(f"{sdir}/rank{rank}.ckpt", "wb") as f:
            f.write(data)
        total += size
    with open(f"{ckpt_dir}/{TRACKER_FILE}", "w") as f:
        f.write(str(step))
    return ckpt_dir, w, junk.nbytes, total


class TestStreamingRestore:
    def test_slice_read_touches_only_owning_shard(self, tmp_path):
        """Fetching rows 0:8 must read rank0's w bytes only — not
        rank1's shard and not the junk leaf."""
        ckpt_dir, w, _, _ = _craft_checkpoint(tmp_path)
        storage = CountingStorage()
        engine = CheckpointEngine(
            ckpt_dir, use_agent=False, storage=storage,
            global_rank=0, world_size=1,
        )
        try:
            step, index, _ = engine.read_shard_metas()
            assert step == 5
            meta_bytes = storage.range_bytes
            sub = engine._read_slice(
                index["w"], (16, 16), "float32",
                (slice(0, 8), slice(0, 16)),
            )
            np.testing.assert_array_equal(sub, w[0:8])
            payload_read = storage.range_bytes - meta_bytes
            assert payload_read == w[0:8].nbytes  # exactly one shard
            # sub-band: rows 2:4 cost 2 rows of bytes, not the entry
            before = storage.range_bytes
            sub2 = engine._read_slice(
                index["w"], (16, 16), "float32",
                (slice(2, 4), slice(0, 16)),
            )
            np.testing.assert_array_equal(sub2, w[2:4])
            assert storage.range_bytes - before == w[2:4].nbytes
            assert not [p for p in storage.full_read_paths
                        if p.endswith('.ckpt')]
        finally:
            engine.close()

    def test_streaming_load_reads_less_than_checkpoint(self, tmp_path):
        """End-to-end load with shardings: bytes read < total
        checkpoint payload (the junk leaf is never fetched), and the
        restored array equals the original across both rank files."""
        ckpt_dir, w, junk_nbytes, total = _craft_checkpoint(tmp_path)
        storage = CountingStorage()
        engine = CheckpointEngine(
            ckpt_dir, use_agent=False, storage=storage,
            global_rank=0, world_size=1,
        )
        mesh = _mesh((8,), ("data",))
        target = NamedSharding(mesh, P("data"))
        try:
            step, state, _ = engine.load(
                {"w": jax.ShapeDtypeStruct((16, 16), jnp.float32)},
                shardings={"w": target},
            )
            assert step == 5
            np.testing.assert_array_equal(np.asarray(state["w"]), w)
            assert state["w"].sharding == target
            assert storage.range_bytes < total  # junk never read
            assert total - storage.range_bytes >= junk_nbytes // 2
            assert not [p for p in storage.full_read_paths
                        if p.endswith('.ckpt')]
        finally:
            engine.close()

    def test_streaming_load_missing_coverage_raises(self, tmp_path):
        """A checkpoint whose shards don't cover the requested slice
        must fail loudly, not return zeros."""
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            TRACKER_FILE,
            pack_shard_file,
        )

        ckpt_dir = str(tmp_path / "holey")
        os.makedirs(f"{ckpt_dir}/1", exist_ok=True)
        w = np.ones((8, 8), np.float32)
        plans = [("w", "float32", (16, 8), ((0, 8), (0, 8)),
                  w.nbytes)]
        entries, size = ckpt_shm.plan_entries(plans)
        payload = bytearray(size)
        payload[entries[0].offset:entries[0].offset + w.nbytes] = (
            w.tobytes())
        with open(f"{ckpt_dir}/1/rank0.ckpt", "wb") as f:
            f.write(pack_shard_file(1, entries, {}, bytes(payload)))
        with open(f"{ckpt_dir}/{TRACKER_FILE}", "w") as f:
            f.write("1")
        engine = CheckpointEngine(
            ckpt_dir, use_agent=False,
            global_rank=0, world_size=1,
        )
        mesh = _mesh((8,), ("data",))
        try:
            with pytest.raises(Exception, match="cover|missing"):
                engine.load(
                    {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)},
                    shardings={
                        "w": NamedSharding(mesh, P("data"))},
                )
        finally:
            engine.close()


class TestCheckpointerStandalone:
    def test_self_hosted_saver(self, tmp_path):
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        mesh = _mesh((8,), ("data",))
        state = _state(mesh)
        ckpt = Checkpointer(str(tmp_path / "ckpt2"))
        saver = ckpt._self_hosted_saver
        try:
            assert ckpt.save_checkpoint(42, state,
                                        storage_type=StorageType.DISK)
            assert ckpt.wait_latest_checkpoint(timeout=20)
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            restored = ckpt.load_checkpoint(like)
            assert ckpt.latest_step() == 42
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(64, dtype=np.float32).reshape(8, 8))
        finally:
            ckpt.close()
            if saver is not None:
                for shm in saver._shms:
                    shm.unlink()


class TestOrbaxCompat:
    def test_export_import_roundtrip(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )
        from dlrover_tpu.trainer.flash_checkpoint.orbax_compat import (
            export_to_orbax,
            import_from_orbax,
        )

        mesh = _mesh((8,), ("data",))
        state = _state(mesh)
        ckpt = Checkpointer(str(tmp_path / "flash"))
        saver = ckpt._self_hosted_saver
        orbax_dir = str(tmp_path / "orbax")
        try:
            assert ckpt.save_checkpoint(
                7, state, storage_type=StorageType.DISK
            )
            assert ckpt.wait_latest_checkpoint(timeout=20)
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state,
            )
            step = export_to_orbax(ckpt, orbax_dir, like)
            assert step == 7
            got_step, restored = import_from_orbax(orbax_dir)
            assert got_step == 7
            np.testing.assert_array_equal(
                np.asarray(restored["w"]), np.asarray(state["w"])
            )
        finally:
            ckpt.close()
            if saver is not None:
                for shm in saver._shms:
                    shm.unlink()


class TestAdviceFixes:
    def test_flush_adopts_staged_dir(self, tmp_path):
        """A memory-only staged checkpoint flushed by the agent before a
        restart must land in the TRAINER's checkpoint dir (carried in
        the staged metadata), not the agent's constructor default."""
        mesh = _mesh((8,), ("data",))
        state = _state(mesh)
        agent_default = str(tmp_path / "agent_default")
        trainer_dir = str(tmp_path / "trainer_dir")
        saver = AsyncCheckpointSaver(
            checkpoint_dir=agent_default,
            local_shard_num=1,
            global_shard_num=1,
            commit_timeout=20.0,
        )
        saver.start()
        engine = CheckpointEngine(trainer_dir, use_agent=True)
        try:
            # Fast path only: never a save_to_storage event.
            assert engine.save_to_memory(7, state)
            assert saver.save_shm_to_storage()
            assert engine.latest_step() == 7  # in trainer_dir
            assert not os.path.exists(
                os.path.join(agent_default, "7"))
        finally:
            engine.close()
            saver.close()

    def test_checkpointer_restores_extra(self, tmp_path):
        from dlrover_tpu.trainer.flash_checkpoint import (
            Checkpointer,
            StorageType,
        )

        mesh = _mesh((8,), ("data",))
        state = _state(mesh)
        ckpt = Checkpointer(str(tmp_path / "ckpt3"))
        try:
            assert ckpt.save_checkpoint(
                9, state, storage_type=StorageType.DISK,
                extra={"sampler": {"epoch": 2, "consumed": 640}})
            assert ckpt.wait_latest_checkpoint(timeout=20)
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            assert ckpt.load_checkpoint(like) is not None
            assert ckpt.last_restored_extra["sampler"] == {
                "epoch": 2, "consumed": 640}
        finally:
            ckpt.close()
