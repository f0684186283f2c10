"""ElasticTrainer fixed-global-batch semantics + checkpointable sampler."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticDataLoader,
    ElasticDistributedSampler,
    ElasticTrainer,
    gradient_accumulation_steps,
)


# The meshes the cells run or will run (fsdp=4 is
# mistral-7b-host4.fsdp4's; data=4 and data=2 x fsdp=2 are those of
# the planned gpt2-124m.data4 and of a re-layout, ROADMAP R1-R2),
# each with what is left of it when half the chips go.
_MESHES = {
    "data4": {"data": 4},
    "fsdp4": {"fsdp": 4},
    "data2xfsdp2": {"data": 2, "fsdp": 2},
}
_HALVED = {
    "data4": {"data": 2},
    "fsdp4": {"fsdp": 2},
    "data2xfsdp2": {"fsdp": 2},
}
_MESH_NAMES = list(_MESHES)


def _mesh_of(axes):
    n = math.prod(axes.values())
    return build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def _mesh(data=4):
    return _mesh_of({"data": data})


# A two-layer MLP whose logical axes shard every leaf but ``b1`` over
# ``fsdp`` under the default rules ("embed" -> fsdp), as the models'
# weights are on the chip.
_MLP_AXES = {
    "w1": ("embed", "mlp"),
    "b1": ("mlp",),
    "w2": ("mlp", "embed"),
    "b2": ("embed",),
}


def _mlp_init(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": 0.3 * jax.random.normal(k1, (8, 16)),
        "b1": jnp.zeros((16,)),
        "w2": 0.3 * jax.random.normal(k2, (16, 8)),
        "b2": jnp.zeros((8,)),
    }


def _mlp_loss(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)


def _mlp_data(n=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = np.tanh(x @ rng.normal(size=(8, 8))).astype(np.float32)
    return x, y


def _sharded_state(mesh, opt):
    """(params, opt_state) made on the mesh by ``make_sharded_init``;
    on a mesh with an ``fsdp`` axis the weights and their moments are
    really split over it."""
    from dlrover_tpu.trainer.step import make_sharded_init

    init, _ = make_sharded_init(mesh, _mlp_init, _MLP_AXES, opt)
    params, opt_state = init(jax.random.PRNGKey(0))
    if mesh.shape.get("fsdp", 1) > 1:
        assert params["w1"].sharding.spec == P("fsdp", None)
        assert len(params["w1"].addressable_shards) == mesh.size
        assert params["w1"].addressable_shards[0].data.shape == (
            8 // mesh.shape["fsdp"], 16,
        )
    return params, opt_state


def _sharded_trainer(mesh_name, accum, **kw):
    """Trainer + state on one of ``_MESHES`` at a global batch of 32
    rows split into ``accum`` microbatches."""
    mesh = _mesh_of(_MESHES[mesh_name])
    opt = optax.sgd(0.1, momentum=0.9)
    tr = ElasticTrainer(
        mesh, _mlp_loss, opt, global_batch_size=32,
        micro_batch_size=32 // (4 * accum), **kw,
    )
    assert tr.accum_steps == accum and tr.num_shards == 4
    return tr, opt, _sharded_state(mesh, opt)


def _linear_loss(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _toy_data(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, 1)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(n, 1)).astype(np.float32)
    return x, y


def test_accum_steps_keeps_global_batch():
    # 8 shards x micro 4 = 32/step -> accum 4 for global 128
    assert gradient_accumulation_steps(128, 4, 8) == 4
    # losing half the shards doubles accumulation, global stays 128
    assert gradient_accumulation_steps(128, 4, 4) == 8
    # non-divisible rounds UP (effective batch never shrinks)
    assert gradient_accumulation_steps(100, 4, 8) == 4


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_accumulated_step_equals_big_batch_step(mesh_name, accum):
    """accum microbatches must produce the same update as one big
    batch (the whole point of fixed-global-batch elasticity), with
    the state split over the mesh as it is on the chip."""
    x, y = _mlp_data(32)
    # donate_state=False: the state is read again below for the
    # big-batch reference (the documented donation escape hatch).
    tr, opt, (params, opt_state) = _sharded_trainer(
        mesh_name, accum, donate_state=False
    )
    p1, _, loss1 = tr.train_step(params, opt_state, x, y)

    # one big-batch step on the same data, on one device
    host = jax.device_get(params)
    loss_big, grads = jax.value_and_grad(_mlp_loss)(host, x, y)
    updates, _ = opt.update(grads, opt.init(host), host)
    p2 = optax.apply_updates(host, updates)

    np.testing.assert_allclose(loss1, loss_big, rtol=1e-5)
    for name in p2:
        assert p1[name].sharding == params[name].sharding
        np.testing.assert_allclose(
            p1[name], p2[name], rtol=1e-5, atol=1e-6
        )


@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_host_and_prestaged_batches_take_the_same_step(mesh_name):
    """A numpy batch handed to ``train_step`` and the arrays
    ``shard_microbatches`` makes of it (what the Prefetcher's worker
    hands over) give bitwise equal losses and parameters."""
    x, y = _mlp_data(32)
    tr, _, (params, opt_state) = _sharded_trainer(
        mesh_name, 4, donate_state=False
    )
    p_host, _, l_host = tr.train_step(params, opt_state, x, y)
    tok, tgt = tr.shard_microbatches(x, y)
    assert tok.shape == (4, 8, 8) and isinstance(tok, jax.Array)
    p_dev, _, l_dev = tr.train_step(params, opt_state, tok, tgt)
    np.testing.assert_array_equal(
        jax.device_get(l_host), jax.device_get(l_dev)
    )
    for name in p_host:
        np.testing.assert_array_equal(
            jax.device_get(p_host[name]), jax.device_get(p_dev[name])
        )
    # and the microbatches are the rows in order, not a permutation
    np.testing.assert_array_equal(
        jax.device_get(tok).reshape(32, 8), x
    )


@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_one_step_program(mesh_name):
    """However the batch arrives, the trainer compiles ONE program
    (the CPU twin of the benchmark's ``step_programs.train``): the
    state a step returns is laid out as the state it took, and a host
    batch is staged under the sharding a pre-staged one has."""
    x, y = _mlp_data(32)
    tr, _, (params, opt_state) = _sharded_trainer(mesh_name, 4)
    want = jax.tree.map(lambda a: a.sharding, (params, opt_state))
    for _ in range(3):
        params, opt_state, _ = tr.train_step(params, opt_state, x, y)
    for _ in range(3):
        params, opt_state, _ = tr.train_step(
            params, opt_state, *tr.shard_microbatches(x, y)
        )
    assert tr._compiled._cache_size() == 1
    assert jax.tree.map(lambda a: a.sharding, (params, opt_state)) == want


@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_world_shrink_same_global_batch(mesh_name):
    """The mesh and what is left of it when half the chips go apply
    the same global batch and produce the same parameters."""
    x, y = _mlp_data(32)
    opt = optax.sgd(0.1, momentum=0.9)

    results = []
    for axes in (_MESHES[mesh_name], _HALVED[mesh_name]):
        mesh = _mesh_of(axes)
        tr = ElasticTrainer(
            mesh, _mlp_loss, opt, global_batch_size=32,
            micro_batch_size=4,
        )
        assert tr.samples_per_step == 32
        assert tr.accum_steps == 8 // mesh.size
        p, _, _ = tr.train_step(*_sharded_state(mesh, opt), x, y)
        results.append(jax.device_get(p))
    for a, b in zip(jax.tree.leaves(results[0]), jax.tree.leaves(results[1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_training_converges():
    x, y = _toy_data(64)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    opt = optax.adam(0.05)
    tr = ElasticTrainer(
        _mesh(2), _linear_loss, opt, global_batch_size=64,
        micro_batch_size=8,
    )
    opt_state = opt.init(params)
    losses = []
    for _ in range(30):
        # np host batch -> staged in train_step; state donated and
        # rebound each iteration (the intended steady-state shape).
        params, opt_state, loss = tr.train_step(
            params, opt_state, x, y
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1


# -- sampler ---------------------------------------------------------------


def test_sampler_shards_partition_epoch():
    samplers = [
        ElasticDistributedSampler(100, num_shards=4, shard_rank=r, seed=7)
        for r in range(4)
    ]
    seen = []
    for s in samplers:
        seen.extend(list(s))
    assert len(seen) == 100  # padded 100->100 (divisible)
    assert sorted(seen) == sorted(set(seen))


def test_sampler_resume_after_world_change_no_replay():
    """Consume 40 samples on 4 shards, checkpoint, resume on 2 shards:
    the union of samples seen must cover the epoch exactly once."""
    first = [
        ElasticDistributedSampler(96, num_shards=4, shard_rank=r, seed=3)
        for r in range(4)
    ]
    seen = []
    iters = [iter(s) for s in first]
    for _ in range(10):  # 10 rounds x 4 shards = 40 samples
        for it in iters:
            seen.append(next(it))
    state = first[0].state_dict()
    assert state["consumed"] == 40

    resumed = []
    for r in range(2):
        s = ElasticDistributedSampler(96, num_shards=2, shard_rank=r, seed=3)
        s.load_state_dict(state)
        resumed.extend(list(s))
    total = seen + resumed
    assert sorted(total) == list(range(96))


def test_sampler_reshuffles_by_epoch():
    s = ElasticDistributedSampler(50, num_shards=1, shard_rank=0, seed=1)
    e0 = list(s)
    s.set_epoch(1)
    e1 = list(s)
    assert e0 != e1
    assert sorted(e0) == sorted(e1)


# -- donation --------------------------------------------------------------


def _run_trajectory(donate: bool, steps: int = 6):
    x, y = _toy_data(32, seed=5)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    opt = optax.adam(0.05)
    tr = ElasticTrainer(
        _mesh(2), _linear_loss, opt, global_batch_size=32,
        micro_batch_size=4, donate_state=donate,
    )
    opt_state = opt.init(params)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = tr.train_step(
            params, opt_state, x, y
        )
        losses.append(jax.device_get(loss))
    return np.asarray(losses), jax.device_get(params["w"])


def test_donation_numerics_parity():
    """The in-place (donated) step must be BITWISE identical to the
    copying step: donation changes buffer lifetime, not math."""
    losses_d, w_d = _run_trajectory(donate=True)
    losses_c, w_c = _run_trajectory(donate=False)
    np.testing.assert_array_equal(losses_d, losses_c)
    np.testing.assert_array_equal(w_d, w_c)


@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_donation_deletes_inputs_and_escape_hatch(mesh_name):
    x, y = _mlp_data(32)

    tr, _, (params, opt_state) = _sharded_trainer(mesh_name, 4)
    assert tr.donate_state  # in-place update is the default
    old = jax.tree.leaves((params, opt_state))
    tr.train_step(params, opt_state, x, y)
    # XLA really updated in place: every shard of every leaf, the
    # moments with the weights
    assert all(leaf.is_deleted() for leaf in old)

    tr2, _, (params, opt_state) = _sharded_trainer(
        mesh_name, 4, donate_state=False
    )
    tr2.train_step(params, opt_state, x, y)
    # escape hatch: alias freely
    assert not any(
        leaf.is_deleted() for leaf in jax.tree.leaves((params, opt_state))
    )
    _ = params["w1"] + 1


# -- host-batch dispatch ---------------------------------------------------


def test_host_batch_dispatch_by_type_not_rank():
    """np.ndarray batches of ANY rank get staged; device arrays from
    shard_microbatches are fed through untouched (no re-staging)."""

    def loss3(params, x, y):  # x: [B, 4, 2] host batch (rank 3)
        flat = x.reshape((x.shape[0], -1))
        pred = flat @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    rng = np.random.default_rng(2)
    x = rng.normal(size=(16, 4, 2)).astype(np.float32)
    y = rng.normal(size=(16, 1)).astype(np.float32)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    opt = optax.sgd(0.1)
    tr = ElasticTrainer(
        _mesh(2), loss3, opt, global_batch_size=16,
        micro_batch_size=2, donate_state=False,
    )
    # rank-3 host batch: the old ndim==2 heuristic skipped staging
    p1, _, l1 = tr.train_step(params, opt.init(params), x, y)

    # pre-staged device arrays take the no-restage path, same result
    tok, tgt = tr.shard_microbatches(x, y)
    assert isinstance(tok, jax.Array)
    p2, _, l2 = tr.train_step(params, opt.init(params), tok, tgt)
    np.testing.assert_array_equal(
        jax.device_get(l1), jax.device_get(l2)
    )

    # a flat [N, ...] DEVICE batch (the pre-change jnp.asarray calling
    # convention) must fail loudly, pointing at shard_microbatches —
    # not error deep in lax.scan or silently mis-microbatch
    with pytest.raises(ValueError, match="pre-staged"):
        tr.train_step(
            params, opt.init(params), jnp.asarray(x), jnp.asarray(y)
        )


# -- async reporting -------------------------------------------------------


def test_async_reporter_exactly_once_in_order():
    from dlrover_tpu.trainer.async_metrics import AsyncScalarReporter

    class Lazy:
        """Device-scalar stand-in whose readiness we control."""

        def __init__(self, v):
            self.v = v
            self.ready = False

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None):  # jax.device_get fallback
            return np.asarray(self.v, dtype=dtype)

    got = []
    rep = AsyncScalarReporter(
        lambda step, v: got.append((step, v)), max_pending=3
    )
    vals = [Lazy(float(i)) for i in range(1, 7)]
    for i, v in enumerate(vals, start=1):
        rep.offer(i, v)
    # nothing ready, deque bounded at 3: the oldest were force-drained
    assert len(rep) == 3
    assert [s for s, _ in got] == [1, 2, 3]
    vals[3].ready = True  # step 4 finishes "on device"
    rep.drain_ready()
    assert [s for s, _ in got] == [1, 2, 3, 4]
    assert rep.flush() == 2  # tail delivered at checkpoint/shutdown
    assert [s for s, _ in got] == [1, 2, 3, 4, 5, 6]
    assert [v for _, v in got] == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert rep.flush() == 0  # idempotent: nothing re-emitted


def test_trainer_reports_every_step_one_late_then_flush():
    x, y = _toy_data(32)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    opt = optax.sgd(0.1)
    reports = []
    tr = ElasticTrainer(
        _mesh(2), _linear_loss, opt, global_batch_size=32,
        micro_batch_size=4, report_fn=reports.append,
    )
    opt_state = opt.init(params)
    for _ in range(5):
        params, opt_state, _ = tr.train_step(params, opt_state, x, y)
    tr.flush_metrics()
    assert [r.step for r in reports] == [1, 2, 3, 4, 5]
    assert all(np.isfinite(r.loss) for r in reports)
    assert all(r.global_batch_size == 32 for r in reports)
    tr.flush_metrics()  # no duplicates on a second flush
    assert [r.step for r in reports] == [1, 2, 3, 4, 5]


# -- zero-sync hot loop ----------------------------------------------------


@pytest.mark.parametrize("mesh_name", _MESH_NAMES)
def test_hot_loop_no_host_sync_under_transfer_guard(mesh_name):
    """Steady-state tripwire: with pre-staged inputs, train_step plus
    async reporting performs NO device<->host transfer. Enforced two
    ways — jax.transfer_guard("disallow") (live on real accelerators;
    the CPU backend exempts same-memory transfers) and a patched
    Array.__float__ that turns any implicit scalar fetch into an
    error on every backend."""
    from jax._src import array as jax_array

    x, y = _mlp_data(32)
    reports = []
    tr, _, (params, opt_state) = _sharded_trainer(
        mesh_name, 4, report_fn=reports.append
    )
    batches = [tr.shard_microbatches(x, y) for _ in range(4)]
    # step 1 pays the compile; the guard covers steady state only
    params, opt_state, _ = tr.train_step(params, opt_state, *batches[0])

    def _boom(self):
        raise AssertionError(
            "implicit device->host sync (float(arr)) in the hot loop"
        )

    orig = jax_array.ArrayImpl.__float__
    jax_array.ArrayImpl.__float__ = _boom
    try:
        with jax.transfer_guard("disallow"):
            for tok, tgt in batches[1:]:
                params, opt_state, loss = tr.train_step(
                    params, opt_state, tok, tgt
                )
                assert isinstance(loss, jax.Array)
            # the tripwire itself is live:
            with pytest.raises(AssertionError, match="hot loop"):
                float(loss)
    finally:
        jax_array.ArrayImpl.__float__ = orig
    tr.flush_metrics()
    assert [r.step for r in reports] == [1, 2, 3, 4]


def test_step_sources_free_of_host_syncs():
    """AST tripwire: the code that BUILDS the jitted step, and the
    staging in front of it, must contain no host-sync calls — float(),
    .item(), np.asarray, jax.device_get, block_until_ready. The
    runtime transfer-guard test catches dynamic syncs; this catches
    one added behind a rarely-hit branch."""
    import ast
    import inspect
    import textwrap

    # int() is allowed: static shape arithmetic (int(np.prod(shape)))
    # never touches device buffers; float()/bool() on a traced value
    # are the classic implicit-sync shapes.
    FORBIDDEN_CALLS = {"float", "bool"}
    FORBIDDEN_ATTRS = {
        "item", "asarray", "device_get", "block_until_ready",
        "tolist",
    }

    def audit(fn, allowed_attrs=()):
        where = fn.__name__
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name):
                assert f.id not in FORBIDDEN_CALLS, (
                    f"{where}:{node.lineno}: host sync {f.id}() in "
                    "the jitted step path"
                )
            if isinstance(f, ast.Attribute):
                assert (
                    f.attr not in FORBIDDEN_ATTRS
                    or f.attr in allowed_attrs
                ), (
                    f"{where}:{node.lineno}: host sync .{f.attr}() "
                    "in the jitted step path"
                )

    audit(ElasticTrainer._build_step)
    audit(ElasticTrainer._wrap_flat_step)
    # The staging's multi-process branch views its input, a HOST
    # array by contract (each process's own samples), with
    # np.asarray before make_array_from_process_local_data: no device
    # buffer is read there.
    audit(ElasticTrainer.shard_microbatches, allowed_attrs={"asarray"})


def test_dataloader_batches():
    data = np.arange(40, dtype=np.float32).reshape(20, 2)
    sampler = ElasticDistributedSampler(
        20, num_shards=2, shard_rank=0, shuffle=False
    )
    dl = ElasticDataLoader(data, batch_size=5, sampler=sampler)
    batches = list(dl)
    assert len(batches) == 2
    assert batches[0].shape == (5, 2)
    # shard 0 takes even positions when unshuffled
    np.testing.assert_array_equal(batches[0][:, 0], [0, 4, 8, 12, 16])
