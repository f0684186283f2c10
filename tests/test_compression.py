"""Compressed gradient sync: accuracy bounds and training parity."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.parallel.compression import (
    bucket_plan,
    bucketed_psum_mean,
    compressed_psum_mean,
    make_compressed_train_step,
    make_overlapped_train_step,
    overlap_sync_bytes_per_element,
    sync_bytes_per_element,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from jax import shard_map
from jax.sharding import PartitionSpec as P


@pytest.mark.parametrize("bits", [8, 4])
def test_compressed_mean_close_to_exact(bits):
    mesh = build_mesh(MeshConfig(data=8))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096), jnp.float32)

    fn = shard_map(
        functools.partial(
            compressed_psum_mean, axis_name="data", bits=bits,
            block=256, min_size=0
        ),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
        check_vma=False,
    )
    got = jax.jit(fn)(x)
    # Every device's row equals the mean of all rows (then re-sharded
    # back along the axis: each shard holds the same mean values).
    want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    err = np.abs(np.asarray(got - want))
    # error bounded by half a quantization level of the per-block max
    bound = np.abs(np.asarray(want)).max() / (127.0 if bits == 8 else 7.0)
    assert err.max() <= bound + 1e-6


def test_compressed_mean_odd_sizes():
    mesh = build_mesh(MeshConfig(data=8))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 123), jnp.float32)
    fn = shard_map(
        functools.partial(
            compressed_psum_mean, axis_name="data", bits=8, block=64,
            min_size=0
        ),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
        check_vma=False,
    )
    got = jax.jit(fn)(x)
    want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2
    )


def test_compressed_train_step_converges_like_exact():
    """Toy regression: compressed-sync training tracks exact-psum
    training to quantization tolerance."""
    mesh = build_mesh(MeshConfig(data=8))
    d = 512
    w_true = jax.random.normal(jax.random.PRNGKey(2), (d,))
    xs = jax.random.normal(jax.random.PRNGKey(3), (64, d))
    ys = xs @ w_true

    def loss_fn(params, x, y):
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    opt = optax.sgd(0.05)

    step_c = make_compressed_train_step(mesh, loss_fn, opt, bits=8)

    def run(step):
        # fresh params per run: the compressed step donates its inputs
        p = {"w": jnp.zeros((d,))}
        s = opt.init(p)
        for _ in range(40):
            p, s, m = step(p, s, xs, ys)
        return p, float(m["loss"])

    p_c, l_c = run(step_c)

    # exact reference (plain pmean data parallel)
    def exact_step(p, s, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, {"loss": loss}

    p_e, l_e = run(jax.jit(exact_step))
    assert l_c < 1e-2  # converged
    np.testing.assert_allclose(
        np.asarray(p_c["w"]), np.asarray(p_e["w"]), atol=5e-2
    )


def test_small_leaves_fall_back_to_exact_pmean():
    """Leaves below min_size skip quantization entirely (a bias would
    otherwise pad to n*block and lose precision for nothing)."""
    mesh = build_mesh(MeshConfig(data=8))
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 64), jnp.float32)
    fn = shard_map(
        functools.partial(
            compressed_psum_mean, axis_name="data", bits=4, block=1024
        ),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
        check_vma=False,
    )
    got = jax.jit(fn)(x)
    want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-6
    )


def test_int4_wire_format_is_packed():
    """The 4-bit all-gather payload must be half the int8 one (two
    nibbles per byte) — the README's 'int4 compressed sync' claim."""
    from dlrover_tpu.ops.quantization import (
        quantize_blockwise_4bit_ref,
        quantize_blockwise_ref,
    )

    x = jnp.ones((4096,), jnp.float32)
    q8, _, _ = quantize_blockwise_ref(x, 1024)
    q4, _, _ = quantize_blockwise_4bit_ref(x, 1024)
    assert q4.size * q4.dtype.itemsize == q8.size * q8.dtype.itemsize // 2


def test_compressed_sync_on_multislice_outer_axis():
    """The target topology: a 2-slice mesh whose outer data axis
    crosses DCN — compressed sync must be numerically sound there."""
    mesh = build_mesh(
        MeshConfig(data=4, fsdp=2, num_slices=2),
        slice_ids=[i // 4 for i in range(8)],
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 4096), jnp.float32)
    fn = shard_map(
        functools.partial(
            compressed_psum_mean, axis_name="data", bits=8,
            block=512, min_size=0,
        ),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
        check_vma=False,
    )
    got = jax.jit(fn)(x)
    want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    err = np.abs(np.asarray(got - want))
    bound = np.abs(np.asarray(want)).max() / 127.0
    assert err.max() <= bound + 1e-6


def test_sync_bytes_accounting():
    assert sync_bytes_per_element(8) == 3.0  # vs 4.0 baseline
    assert sync_bytes_per_element(4) == 2.5
    assert sync_bytes_per_element(None) == 4.0  # exact sync


@pytest.mark.parametrize("bits", [8, 4])
def test_compressed_psum_mean_gradient_parity(bits):
    """Gradients SYNCED through compressed_psum_mean (the thing the
    train steps actually do) track the exact-pmean gradients within
    the per-bit quantization tolerance on the CPU mesh."""
    mesh = build_mesh(MeshConfig(data=8))
    d = 2048
    xs = jax.random.normal(jax.random.PRNGKey(7), (8, 16, d))
    ys = jax.random.normal(jax.random.PRNGKey(8), (8, 16))

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    def synced_grad(sync):
        def f(x, y):
            w = jnp.zeros((d,))
            g = jax.grad(loss_fn)(w, x, y)
            return sync(g)

        return shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P(), check_vma=False,
        )

    exact = jax.jit(
        synced_grad(lambda g: jax.lax.pmean(g, "data"))
    )(xs, ys)
    comp = jax.jit(
        synced_grad(
            functools.partial(
                compressed_psum_mean, axis_name="data", bits=bits,
                block=256, min_size=0,
            )
        )
    )(xs, ys)
    err = np.abs(np.asarray(comp - exact))
    bound = np.abs(np.asarray(exact)).max() / (
        127.0 if bits == 8 else 7.0
    )
    assert err.max() <= bound + 1e-6


# -- bucketed overlap ------------------------------------------------------


class TestBucketPlan:
    def test_covers_every_leaf_exactly_once_in_order(self):
        leaves = [jnp.zeros((n,)) for n in (10, 20, 5000, 3, 7)]
        plan = bucket_plan(leaves, bucket_bytes=1 << 10)
        flat = [i for b in plan for i in b]
        assert flat == list(range(len(leaves)))

    def test_respects_byte_bound_except_oversized_leaf(self):
        leaves = [
            jnp.zeros((100,)),  # 400 B
            jnp.zeros((100,)),  # 400 B
            jnp.zeros((1000,)),  # 4000 B > bound: own bucket
            jnp.zeros((50,)),  # 200 B
        ]
        plan = bucket_plan(leaves, bucket_bytes=1000)
        assert plan == [[0, 1], [2], [3]]

    def test_dtype_homogeneous_buckets(self):
        leaves = [
            jnp.zeros((10,), jnp.float32),
            jnp.zeros((10,), jnp.int32),
            jnp.zeros((10,), jnp.int32),
        ]
        plan = bucket_plan(leaves, bucket_bytes=1 << 20)
        assert plan == [[0], [1, 2]]

    def test_works_on_shape_dtype_structs(self):
        leaves = [
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.float32),
        ]
        plan = bucket_plan(leaves, bucket_bytes=1 << 20)
        assert plan == [[0, 1]]


@pytest.mark.parametrize("bits", [None, 8, 4])
def test_bucketed_psum_mean_matches_exact_tree_mean(bits):
    mesh = build_mesh(MeshConfig(data=8))
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(9), (8, 700)),
        "b": jax.random.normal(jax.random.PRNGKey(10), (8, 9)),
        "h": jax.random.normal(jax.random.PRNGKey(11), (8, 4096)),
    }
    fn = shard_map(
        functools.partial(
            bucketed_psum_mean, axis_name="data",
            bucket_bytes=2048, bits=bits, block=64, min_size=0,
        ),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
        check_vma=False,
    )
    got = jax.jit(fn)(tree)
    for k, v in tree.items():
        want = jnp.broadcast_to(
            jnp.mean(v, axis=0, keepdims=True), v.shape
        )
        err = np.abs(np.asarray(got[k] - want)).max()
        tol = 1e-6 if bits is None else np.abs(
            np.asarray(want)
        ).max() / (127.0 if bits == 8 else 7.0) + 1e-6
        assert err <= tol, (k, bits, err)


def test_overlap_bytes_match_bucketed_plan_accounting():
    """The satellite contract: sync_bytes_per_element composes with
    the bucket plan — buckets partition the gradient elements exactly,
    so the overlapped schedule's per-step volume is
    accum * sum(bucket elements) * sync_bytes_per_element(bits)."""
    leaves = [jnp.zeros((n,)) for n in (300, 50, 8000, 12)]
    n_el = sum(int(leaf.size) for leaf in leaves)
    plan = bucket_plan(leaves, bucket_bytes=2048)
    plan_el = sum(
        int(leaves[i].size) for b in plan for i in b
    )
    assert plan_el == n_el  # partition: no element dropped/duplicated
    for bits, accum, per_el in (
        (None, 1, 4.0),   # exact serial baseline
        (8, 1, 3.0),      # compressed, no accumulation
        (8, 2, 6.0),      # overlap pays per microbatch
        (4, 3, 7.5),
    ):
        assert overlap_sync_bytes_per_element(bits, accum) == per_el
        assert (
            overlap_sync_bytes_per_element(bits, accum) * plan_el
            == per_el * n_el
        )


@pytest.mark.parametrize("bits", [None, 8])
def test_overlapped_accum_step_matches_serial_reference(bits):
    """make_overlapped_train_step with accum>1 (per-microbatch
    bucketed reduce inside the scan) produces the same update as the
    serial accumulate-then-reduce reference — exactly for bits=None,
    within quantization tolerance for int8."""
    mesh = build_mesh(MeshConfig(data=8))
    d = 256
    accum = 2
    w_true = jax.random.normal(jax.random.PRNGKey(12), (d,))
    xs = jax.random.normal(jax.random.PRNGKey(13), (accum, 32, d))
    ys = jnp.einsum("abd,d->ab", xs, w_true)

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    opt = optax.sgd(0.05)
    step = make_overlapped_train_step(
        mesh, loss_fn, opt, accum_steps=accum, bucket_mb=0.0005,
        bits=bits, min_size=0, block=64, donate=False,
    )
    p0 = {"w": jnp.zeros((d,))}
    p_o, _, m_o = step(p0, opt.init(p0), xs, ys)

    g_acc = jax.tree.map(jnp.zeros_like, p0)
    loss_sum = 0.0
    for k in range(accum):
        lk, gk = jax.value_and_grad(loss_fn)(p0, xs[k], ys[k])
        g_acc = jax.tree.map(
            lambda a, g: a + g / accum, g_acc, gk
        )
        loss_sum = loss_sum + lk
    u, _ = opt.update(g_acc, opt.init(p0), p0)
    p_r = optax.apply_updates(p0, u)

    np.testing.assert_allclose(
        float(m_o["loss"]), float(loss_sum / accum), rtol=1e-5
    )
    tol = dict(atol=1e-6, rtol=1e-5) if bits is None else dict(
        atol=5e-2, rtol=0.0
    )
    np.testing.assert_allclose(
        np.asarray(p_o["w"]), np.asarray(p_r["w"]), **tol
    )


def test_overlapped_flat_step_trains():
    """accum_steps=1 (flat batch, the auto_accelerate build path)
    still converges with bucketed int8 sync + donation."""
    mesh = build_mesh(MeshConfig(data=8))
    d = 512
    w_true = jax.random.normal(jax.random.PRNGKey(14), (d,))
    xs = jax.random.normal(jax.random.PRNGKey(15), (64, d))
    ys = xs @ w_true

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    opt = optax.sgd(0.05)
    step = make_overlapped_train_step(
        mesh, loss_fn, opt, bucket_mb=0.001, bits=8, min_size=0,
        block=256,
    )
    p = {"w": jnp.zeros((d,))}
    s = opt.init(p)
    for _ in range(40):
        p, s, m = step(p, s, xs, ys)
    assert float(m["loss"]) < 1e-2


def test_step_metrics_contract_matches_make_train_step():
    """Every strategy's step returns {"loss", "grad_norm"} — a caller
    reading metrics["grad_norm"] must not crash only when the search
    happens to pick an overlap/compressed strategy."""
    mesh = build_mesh(MeshConfig(data=8))
    xs = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
    ys = xs @ jax.random.normal(jax.random.PRNGKey(4), (8,))

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    opt = optax.sgd(0.1)
    p = {"w": jnp.zeros((8,))}
    for step in (
        make_compressed_train_step(mesh, loss_fn, opt, bits=8,
                                   min_size=0, block=256,
                                   donate=False),
        make_overlapped_train_step(mesh, loss_fn, opt,
                                   bucket_mb=0.001, donate=False),
    ):
        _, _, m = step(p, opt.init(p), xs, ys)
        assert set(m) == {"loss", "grad_norm"}
        assert float(m["grad_norm"]) > 0.0


def test_accum_without_overlap_rejected():
    mesh = build_mesh(MeshConfig(data=8))
    with pytest.raises(ValueError, match="overlap"):
        make_compressed_train_step(
            mesh, lambda p, x, y: 0.0, optax.sgd(0.1), accum_steps=2
        )


def test_compressed_sync_on_two_slice_mesh_converges():
    """Integration (VERDICT r2 item 9): the compressed gradient sync
    running on a mesh whose data axis spans TWO virtual slices — the
    quantized all-gather is the collective that rides DCN on real
    multi-slice hardware — converges in parity with the exact step."""
    mesh = build_mesh(
        MeshConfig(data=8, num_slices=2),
        slice_ids=[i // 4 for i in range(8)],
    )
    d = 512
    w_true = jax.random.normal(jax.random.PRNGKey(5), (d,))
    xs = jax.random.normal(jax.random.PRNGKey(6), (64, d))
    ys = xs @ w_true

    def loss_fn(params, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = optax.sgd(0.05)
    step_c = make_compressed_train_step(mesh, loss_fn, opt, bits=8)

    def exact_step(p, s, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, {"loss": loss}

    def run(step):
        p = {"w": jnp.zeros((d,))}
        s = opt.init(p)
        for _ in range(40):
            p, s, m = step(p, s, xs, ys)
        return p, float(m["loss"])

    p_c, l_c = run(step_c)
    p_e, l_e = run(jax.jit(exact_step))
    assert l_c < 1e-2
    np.testing.assert_allclose(
        np.asarray(p_c["w"]), np.asarray(p_e["w"]), atol=5e-2
    )
