"""Prefetch pipeline: ordering, backpressure, shutdown drain, sampler
state with batches in flight, device staging (H2D in the worker under
the step's sharding), and the compute/staging overlap bench."""

import threading
import time

import numpy as np
import pytest

from dlrover_tpu.data.prefetch import (
    Prefetcher,
    free_device_buffers,
    make_input_pipeline,
    prefetch_depth,
)
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticDataLoader,
    ElasticDistributedSampler,
)


class CountingSource:
    """Re-iterable source that records how many items were pulled."""

    def __init__(self, n, gate: threading.Event = None):
        self.n = n
        self.pulled = 0
        self.gate = gate

    def __iter__(self):
        for i in range(self.n):
            if self.gate is not None:
                self.gate.wait(5.0)
            self.pulled += 1
            yield i


def test_delivers_in_order_through_stage_fn():
    with Prefetcher(
        CountingSource(10), stage_fn=lambda x: x * 2, depth=3
    ) as pf:
        got = list(pf)
    assert got == [2 * i for i in range(10)]
    assert pf.delivered == 10


def test_end_of_stream_raises_stopiteration_repeatedly():
    pf = Prefetcher(CountingSource(2), depth=2)
    assert next(pf) == 0 and next(pf) == 1
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):
        next(pf)  # stays exhausted, no hang
    pf.close()


def test_backpressure_bounds_readahead():
    """The worker may run at most ``depth`` staged batches + 1 being
    staged ahead of the consumer — never the whole dataset."""
    src = CountingSource(100)
    pf = Prefetcher(src, depth=2)
    time.sleep(0.3)  # worker free-runs against the bounded queue
    assert src.pulled <= 2 + 1
    for _ in range(10):
        next(pf)
    time.sleep(0.2)
    assert src.pulled <= 10 + 2 + 1
    pf.close()


def test_close_drains_and_stops_worker():
    src = CountingSource(1000)
    pf = Prefetcher(src, depth=2)
    next(pf)
    pf.close()
    assert not pf._thread.is_alive()
    assert pf.dropped >= 1  # staged-but-undelivered were discarded
    assert pf.delivered == 1
    pulled_at_close = src.pulled
    time.sleep(0.15)
    assert src.pulled == pulled_at_close  # nothing pulled after close
    with pytest.raises(RuntimeError):
        next(pf)
    pf.close()  # idempotent


def test_close_from_another_thread_unblocks_consumer():
    """A restart/watchdog thread closing the pipeline must wake a
    consumer blocked on an empty queue, not strand it forever."""
    gate = threading.Event()

    def slow_source():
        gate.wait(2.0)
        yield 1

    pf = Prefetcher(slow_source(), depth=1)
    caught = []

    def consume():
        try:
            next(pf)
        except BaseException as exc:  # noqa: BLE001
            caught.append(exc)

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.2)  # consumer is now blocked in __next__
    pf.close()  # worker still parked in the source: nothing queued
    gate.set()
    t.join(timeout=3.0)
    assert not t.is_alive()
    assert caught and isinstance(caught[0], RuntimeError)
    assert "closed" in str(caught[0])
    # staged == delivered + dropped even under the close race
    assert pf.staged == pf.delivered + pf.dropped


def test_worker_exception_propagates_to_consumer():
    def bad_stage(x):
        if x == 3:
            raise ValueError("boom at 3")
        return x

    pf = Prefetcher(CountingSource(10), stage_fn=bad_stage, depth=2)
    got = []
    with pytest.raises(ValueError, match="boom at 3"):
        for item in pf:
            got.append(item)
    assert got == [0, 1, 2]
    pf.close()


# -- sampler state with batches in flight ----------------------------------


def _loader(n=40, batch=5, shuffle=False):
    data = np.arange(n, dtype=np.int64)
    sampler = ElasticDistributedSampler(
        n, num_shards=1, shard_rank=0, shuffle=shuffle, seed=3
    )
    return (
        ElasticDataLoader(data, batch_size=batch, sampler=sampler),
        sampler,
    )


def test_sampler_state_counts_only_delivered_batches():
    loader, sampler = _loader(n=40, batch=5)
    pf = Prefetcher(loader, depth=3, sampler=sampler)
    assert pf.sampler_state_dict()["consumed"] == 0  # nothing trained
    first = next(pf)
    np.testing.assert_array_equal(first, np.arange(5))
    next(pf)
    # let the worker stage ahead: the RAW sampler now over-counts
    deadline = time.time() + 2.0
    while sampler.state_dict()["consumed"] <= 10 and time.time() < deadline:
        time.sleep(0.01)
    assert sampler.state_dict()["consumed"] > 10  # in-flight counted
    assert pf.sampler_state_dict()["consumed"] == 10  # delivered only
    pf.close()

    # an elastic restart from the checkpointed state replays the
    # staged-but-untrained samples instead of skipping them
    fresh = ElasticDistributedSampler(
        40, num_shards=1, shard_rank=0, shuffle=False, seed=3
    )
    fresh.load_state_dict(pf.sampler_state_dict())
    assert next(iter(fresh)) == 10


def test_auto_epoch_restarts_source_and_bumps_epoch():
    loader, sampler = _loader(n=10, batch=5, shuffle=True)
    pf = Prefetcher(loader, depth=2, sampler=sampler, auto_epoch=True)
    batches = [next(pf) for _ in range(6)]  # 3 epochs of 2 batches
    pf.close()
    assert sampler.epoch >= 2
    e0 = np.concatenate(batches[0:2])
    e1 = np.concatenate(batches[2:4])
    assert sorted(e0.tolist()) == sorted(e1.tolist()) == list(range(10))
    assert e0.tolist() != e1.tolist()  # reshuffled per epoch


def test_auto_epoch_requires_sampler():
    with pytest.raises(ValueError, match="auto_epoch"):
        Prefetcher(CountingSource(3), auto_epoch=True)


def test_zero_batch_epoch_fails_loudly_not_hangs():
    """A dataset smaller than one batch (drop_last) yields zero-batch
    epochs; auto_epoch must raise, not busy-spin the worker while the
    consumer blocks forever."""
    loader, sampler = _loader(n=3, batch=5)  # 3 < 5: no batch, ever
    pf = Prefetcher(loader, depth=2, sampler=sampler, auto_epoch=True)
    with pytest.raises(RuntimeError, match="no batches"):
        next(pf)
    pf.close()


def test_resume_at_epoch_boundary_rolls_not_raises():
    """A checkpoint taken at the end of an epoch restores a sampler
    whose FIRST pass yields nothing — the pipeline must roll into the
    next epoch, not fire the zero-batch guard (only two consecutive
    empty passes are a real error)."""
    loader, sampler = _loader(n=20, batch=5)
    sampler.load_state_dict({"epoch": 0, "consumed": 20, "seed": 3})
    pf = Prefetcher(loader, depth=2, sampler=sampler, auto_epoch=True)
    first = next(pf)  # epoch rolled to 1, fresh pass
    assert first.shape == (5,)
    assert pf.sampler_state_dict()["epoch"] == 1
    pf.close()


def test_make_input_pipeline_keeps_the_names_data_wait_reads():
    """``make_input_pipeline(source, h2d_fn=..., name=...)`` is what
    the benchmark's loop calls, and its ``data_wait_ms.train`` reads
    these span, event and metric names letter for letter."""
    from dlrover_tpu.obs import tracer as tracer_mod
    from dlrover_tpu.obs.metrics import get_registry

    reg = get_registry()
    stage = reg.get("dlrover_prefetch_stage_seconds_total")
    h2d_before = stage.value(phase="h2d")
    waits_before = reg.get("dlrover_train_data_wait_seconds").count()
    tracer = tracer_mod.configure_tracer()
    try:
        pipe = make_input_pipeline(
            CountingSource(3), h2d_fn=lambda b: b * 10, name="train"
        )
        assert isinstance(pipe, Prefetcher)
        assert list(pipe) == [0, 10, 20]
        pipe.close()
        events = tracer.events()
    finally:
        tracer_mod.disable_tracer()
    names = [e["name"] for e in events]
    for name in (
        "trainer.prefetch_stage",
        "trainer.prefetch_h2d",
        "trainer.prefetch_wait",
    ):
        assert names.count(name) == 3, name
    assert all(
        e["pipeline"] == "train"
        for e in events
        if e["name"].startswith("trainer.prefetch_")
    )
    waits = [e for e in events if e["name"] == "trainer.prefetch_wait"]
    assert all({"dur_s", "host_s", "h2d_s"} <= set(e) for e in waits)
    assert stage.value(phase="h2d") > h2d_before
    assert (
        reg.get("dlrover_train_data_wait_seconds").count()
        == waits_before + 3
    )


# -- knobs -----------------------------------------------------------------


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_PREFETCH_DEPTH", "5")
    assert prefetch_depth() == 5
    monkeypatch.setenv("DLROVER_TPU_PREFETCH_DEPTH", "junk")
    assert prefetch_depth() == 2
    monkeypatch.setenv("DLROVER_TPU_PREFETCH_DEPTH", "0")
    assert prefetch_depth() == 1  # clamped
    with pytest.raises(ValueError):
        Prefetcher(CountingSource(1), depth=0)


# -- observability ---------------------------------------------------------


def test_prefetch_emits_trace_events_and_data_wait_metric():
    from dlrover_tpu import obs
    from dlrover_tpu.obs import tracer as tracer_mod

    tracer = tracer_mod.configure_tracer()
    try:
        with Prefetcher(
            CountingSource(3), stage_fn=lambda x: x, depth=2,
            name="obs-test",
        ) as pf:
            assert list(pf) == [0, 1, 2]
        names = [e["name"] for e in tracer.events()]
        assert "trainer.prefetch_start" in names
        assert names.count("trainer.prefetch_stage") == 3
        # exactly one wait per REAL batch: the terminal sentinel
        # fetch must not add a phantom sample
        assert names.count("trainer.prefetch_wait") == 3
        stop = [
            e for e in tracer.events()
            if e["name"] == "trainer.prefetch_stop"
        ][-1]
        assert stop["delivered"] == 3 and stop["dropped"] == 0
    finally:
        tracer_mod.disable_tracer()
    hist = obs.histogram("dlrover_train_data_wait_seconds")
    assert hist.count() >= 3  # every consumer wait was observed


# -- device staging (the device-resident input pipeline) -------------------


def _mesh8():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _batch_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("data"))


def test_worker_h2d_delivers_committed_sharded_device_arrays():
    """The tentpole: the worker finishes with jax.device_put under
    the step's NamedSharding — the queue hands the consumer committed
    device arrays, correctly laid out on the multi-device mesh."""
    import jax

    mesh = _mesh8()
    sharding = _batch_sharding(mesh)

    def source():
        for i in range(4):
            yield np.full((16, 2), i, dtype=np.float32)

    with Prefetcher(
        source(),
        h2d_fn=lambda b: jax.device_put(b, sharding),
        depth=2,
    ) as pf:
        got = list(pf)
    assert len(got) == 4
    for i, arr in enumerate(got):
        assert isinstance(arr, jax.Array)
        assert arr.sharding == sharding
        assert arr.committed
        np.testing.assert_array_equal(
            np.asarray(arr), np.full((16, 2), i, dtype=np.float32)
        )
    # worker-side H2D was timed and attributed
    assert pf.h2d_stage_s_total > 0.0


def test_h2d_runs_in_the_worker_only():
    """``h2d_fn`` never executes on the consumer's thread: every
    batch comes off the queue already placed, whether the consumer
    finds it waiting or blocks for it."""
    import jax

    mesh = _mesh8()
    sharding = _batch_sharding(mesh)
    h2d_threads = []
    gate = threading.Event()

    def h2d(b):
        h2d_threads.append(threading.current_thread())
        return jax.device_put(b, sharding)

    def source():
        for i in range(4):
            if i == 2:
                gate.wait(5.0)  # the consumer blocks for this one
            yield np.full((8, 2), i, dtype=np.float32)

    pf = Prefetcher(source(), h2d_fn=h2d, depth=2)
    first = next(pf)
    assert isinstance(first, jax.Array) and first.sharding == sharding
    next(pf)
    threading.Timer(0.05, gate.set).start()
    got = [next(pf), next(pf)]  # queue empty: waits on the worker
    assert [int(a[0, 0]) for a in got] == [2, 3]
    assert len(h2d_threads) == 4
    assert set(h2d_threads) == {pf._thread}
    assert threading.current_thread() not in h2d_threads
    # and what the worker spent there is what the counters hold
    assert pf.h2d_stage_s_total > 0.0
    assert pf.h2d_wait_s_total <= pf.wait_s_total
    pf.close()


def test_sampler_state_excludes_in_flight_device_batches():
    """Delivered-only sampler snapshots hold when the in-flight
    batches are DEVICE arrays: a checkpoint must replay staged
    device-resident batches too."""
    import jax

    mesh = _mesh8()
    sharding = _batch_sharding(mesh)
    loader, sampler = _loader(n=40, batch=8)
    pf = Prefetcher(
        loader,
        h2d_fn=lambda b: jax.device_put(
            b.astype(np.float32), sharding
        ),
        depth=3,
        sampler=sampler,
    )
    first = next(pf)
    assert isinstance(first, jax.Array)
    deadline = time.time() + 2.0
    while (
        sampler.state_dict()["consumed"] <= 8
        and time.time() < deadline
    ):
        time.sleep(0.01)
    assert sampler.state_dict()["consumed"] > 8  # in-flight on device
    assert pf.sampler_state_dict()["consumed"] == 8  # delivered only
    pf.close()


def test_close_frees_dropped_device_slots():
    """Drain-on-close must hand dropped batches' HBM back eagerly:
    staged-but-undelivered device arrays are delete()d."""
    import jax

    mesh = _mesh8()
    sharding = _batch_sharding(mesh)
    staged_arrays = []

    def h2d(b):
        arr = jax.device_put(b, sharding)
        staged_arrays.append(arr)
        return arr

    def source():
        for _ in range(10):
            yield np.zeros((8, 2), dtype=np.float32)

    pf = Prefetcher(source(), h2d_fn=h2d, depth=3)
    delivered = next(pf)
    # let the worker fill the queue
    deadline = time.time() + 2.0
    while pf.staged < 3 and time.time() < deadline:
        time.sleep(0.01)
    pf.close()
    assert pf.dropped >= 1
    assert not delivered.is_deleted()  # the consumer's batch is HIS
    dropped = [a for a in staged_arrays if a is not delivered]
    assert dropped and all(a.is_deleted() for a in dropped)
    pf.close()  # idempotent under the device-staging path too


def test_free_device_buffers_walks_containers():
    import jax

    a = jax.numpy.zeros((4,))
    b = jax.numpy.zeros((2,))
    free_device_buffers(({"x": a}, [b], "not-an-array", None))
    assert a.is_deleted() and b.is_deleted()
    free_device_buffers(({"x": a}, [b]))  # already-deleted: no raise


def test_worker_h2d_failure_is_loud_not_a_hang():
    """A device_put failure in the worker must surface as a step
    error at the consumer (the _Error relay), never leave the
    consumer blocked on the bounded queue."""

    def bad_h2d(b):
        raise RuntimeError("device_put exploded")

    pf = Prefetcher(
        CountingSource(5), h2d_fn=bad_h2d, depth=2,
    )
    with pytest.raises(RuntimeError, match="device_put exploded"):
        next(pf)
    pf.close()


def test_zero_batch_epoch_guard_under_device_staging():
    """The loud zero-batch-epoch failure still fires when the worker
    ends with a device stage (the guard lives upstream of h2d_fn)."""
    loader, sampler = _loader(n=3, batch=5)  # never fills a batch
    pf = Prefetcher(
        loader,
        h2d_fn=lambda b: b,
        depth=2,
        sampler=sampler,
        auto_epoch=True,
    )
    with pytest.raises(RuntimeError, match="no batches"):
        next(pf)
    pf.close()
    pf.close()  # idempotent


def test_wait_split_attribution_proportional():
    """A consumer wait is split by the worker's
    host vs h2d staging proportion for that batch."""
    gate = threading.Event()

    def slow_source():
        for i in range(3):
            gate.wait(2.0)
            yield i

    def h2d(b):
        time.sleep(0.03)
        return b

    pf = Prefetcher(slow_source(), h2d_fn=h2d, depth=1)
    time.sleep(0.05)
    gate.set()
    next(pf)
    host_w, h2d_w = pf.wait_breakdown()
    assert host_w > 0.0 and h2d_w > 0.0
    assert pf.wait_s_total == pytest.approx(host_w + h2d_w, rel=1e-6)
    pf.close()


# -- the point of it all: overlap ------------------------------------------


def test_device_prefetch_hides_h2d_behind_compute():
    """The acceptance fallback for CPU-only containers: with H2D cost
    H per batch and compute cost C >= H per step, the worker's device
    staging hides H2D almost entirely — the consumer's wait is far
    below the N*H it would pay placing each batch itself."""
    h2d_s = 0.02
    compute_s = 0.03
    n_steps = 8

    def slow_h2d(x):
        time.sleep(h2d_s)
        return x

    pf = Prefetcher(
        CountingSource(n_steps + 2), h2d_fn=slow_h2d, depth=2
    )
    next(pf)  # warmup: pays the initial pipeline fill
    pf.wait_s_total = 0.0
    for _ in range(n_steps):
        time.sleep(compute_s)  # "the XLA step"
        next(pf)
    hidden_wait = pf.wait_s_total
    pf.close()
    sequential = n_steps * h2d_s
    assert pf.h2d_stage_s_total >= 0.9 * (n_steps + 1) * h2d_s
    assert hidden_wait < 0.5 * sequential, (
        f"device prefetch hid only "
        f"{sequential - hidden_wait:.3f}s of {sequential:.3f}s H2D"
    )


def test_prefetch_overlaps_staging_with_compute():
    """CPU microbench for the acceptance bar: with staging cost S per
    batch and compute cost C >= S per step, the steady-state data
    wait must be far below sequential staging (N * S) — the pipeline
    hides staging behind compute."""
    stage_s = 0.02
    compute_s = 0.03
    n_steps = 8

    def slow_stage(x):
        time.sleep(stage_s)
        return x

    pf = Prefetcher(
        CountingSource(n_steps + 2), stage_fn=slow_stage, depth=2
    )
    next(pf)  # warmup: pays the initial pipeline fill
    pf.wait_s_total = 0.0
    for _ in range(n_steps):
        time.sleep(compute_s)  # "the XLA step"
        next(pf)
    data_wait = pf.wait_s_total
    pf.close()
    sequential = n_steps * stage_s
    # generous margin for CI jitter; in practice data_wait is ~0
    assert data_wait < 0.5 * sequential, (
        f"prefetch hid only {sequential - data_wait:.3f}s of "
        f"{sequential:.3f}s staging (waited {data_wait:.3f}s)"
    )
