"""The flash kernels on the layout the projections write,
``flash_attention_wide`` (q ``[B, T, H*D]``, k and v ``[B, T, Hkv*D]``,
a key-value head chosen by the block's index), against the
``[B, T, H, D]`` entry on the same operands with k and v repeated to
the query heads; ``ops/rope.rope_wide`` against ``llama.apply_rope``;
and ``llama.attention_half``'s wide route against its 4-D one.
Interpreted, tiny shapes: 4 query and 2 key-value heads of 128."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.accelerate import remat
from dlrover_tpu.models import llama
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.ops.rope import rope_wide
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.test_flash_attention import flash_module

B, H, HKV, D = 1, 4, 2, 128
BLOCKS = dict(block_q=64, block_k=64, interpret=True)

CASES = {
    # tokens, dtype, flash keywords
    "causal": (256, jnp.float32, dict(causal=True)),
    "causal_bf16": (128, jnp.bfloat16, dict(causal=True)),
    # A band, a sequence padded to the blocks (104 -> 128) and ``lse``
    # with a cotangent of its own, in one program a side.
    "window_padded_lse": (
        104, jnp.float32, dict(causal=True, window=40, return_lse=True)),
}


def _operands(t, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, t, H * D), dtype)
    k = jax.random.normal(ks[1], (B, t, HKV * D), dtype)
    v = jax.random.normal(ks[2], (B, t, HKV * D), dtype)
    g = jax.random.normal(ks[3], (B, t, H * D), dtype)
    g_lse = jax.random.normal(ks[4], (B, H, t), jnp.float32)
    return q, k, v, g, g_lse


def _four_d(q, k, v, **kw):
    """The 4-D entry on the wide operands: views, the repeat, and the
    result back ``[B, T, H*D]``."""
    t = q.shape[1]
    out = flash_attention(
        q.reshape(B, t, H, D),
        jnp.repeat(k.reshape(B, t, HKV, D), H // HKV, axis=2),
        jnp.repeat(v.reshape(B, t, HKV, D), H // HKV, axis=2),
        **kw,
    )
    if kw.get("return_lse"):
        return out[0].reshape(B, t, H * D), out[1]
    return out.reshape(B, t, H * D)


def _wide(q, k, v, **kw):
    return flash_module.flash_attention_wide(
        q, k, v, n_head=H, n_kv_head=HKV, **kw
    )


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_wide_entry_equals_the_4d_entry(case):
    """Forward and all three gradients. The forward, ``lse`` and dv
    are the same kernels on the same blocks in the same order: equal
    to the bit in both dtypes. dq and dk pass through ``delta``, whose
    row sums the wide entry forms as a membership product and the 4-D
    one as ``jnp.sum``: another order of 128 additions, 3e-6 here in
    float32 on gradients of size 1."""
    t, dtype, kw = CASES[case]
    q, k, v, g, g_lse = _operands(t, dtype)
    cot = (g, g_lse) if kw.get("return_lse") else g

    def both(fn):
        out, vjp = jax.vjp(functools.partial(fn, **kw, **BLOCKS), q, k, v)
        return out, vjp(cot)

    want, g_want = jax.jit(lambda: both(_four_d))()
    got, g_got = jax.jit(lambda: both(_wide))()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(_f32(a), _f32(b))
    assert [x.shape for x in g_got] == [q.shape, k.shape, v.shape]
    np.testing.assert_array_equal(_f32(g_got[2]), _f32(g_want[2]))
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for a, b in zip(g_got[:2], g_want[:2]):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(_f32(a), _f32(b), atol=tol, rtol=tol)


def test_wide_entry_refuses_what_it_cannot_read_where_it_lies():
    q, k, v, _, _ = _operands(64, jnp.float32)
    with pytest.raises(ValueError, match="whole"):
        flash_module.flash_attention_wide(q, k, v, n_head=2 * H)  # 64 lanes
    with pytest.raises(ValueError, match="Hkv"):
        flash_module.flash_attention_wide(q, k, v, n_head=H)  # k of 2 heads
    assert flash_module.wide_head_size(128)
    assert flash_module.wide_head_size(256)
    assert not flash_module.wide_head_size(64)
    assert not flash_module.wide_head_size(192)


def test_wide_form_is_the_flash_function_with_its_keywords_and_nothing_else():
    bound = functools.partial(
        functools.partial(flash_attention, causal=True), window=32
    )
    wide = flash_module.wide_form(bound)
    assert wide.func is flash_module.flash_attention_wide
    assert wide.keywords == dict(causal=True, window=32)
    assert flash_module.wide_form(lambda q, k, v: q) is None
    assert flash_module.wide_form(flash_attention) is None
    assert flash_module.wide_form(
        functools.partial(llama.apply_rope, None)
    ) is None


@pytest.mark.parametrize("rot,dtype", [
    (D, jnp.bfloat16), (D // 2, jnp.bfloat16), (D // 2, jnp.float32),
], ids=["whole_bf16", "partial_bf16", "partial_f32"])
def test_rotation_on_the_wide_layout_is_apply_ropes(rot, dtype):
    """``rope_wide`` against ``apply_rope`` on the 4-D view, forward
    and the cotangent back, a table narrower than half a head
    included. Inside a compiled step XLA fuses ``apply_rope``'s
    products and its subtraction and rounds the result once (excess
    precision between them); the kernel does the same by hand, so the
    reference here is ``apply_rope`` in float32 on the tables as it
    rounds them, rounded once: equal but where float32's own last
    place moves a rounding (XLA on the CPU contracts a multiply and
    an add into one operation), so within one place of x's dtype on
    values of size one, and of ``apply_rope`` called op by op, which
    rounds each product, within two."""
    t, heads = 64, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (2, t, heads * D), dtype)
    g = jax.random.normal(ks[1], (2, t, heads * D), dtype)
    angle = jax.random.normal(ks[2], (t, rot // 2)) * 3.0
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    tables = [a.astype(dtype).astype(jnp.float32) for a in (cos, sin)]

    def four_d(x, tables=tables, dtype=dtype):
        y = llama.apply_rope(
            x.astype(jnp.float32).reshape(2, t, heads, D), *tables
        )
        return y.reshape(x.shape).astype(dtype)

    def by_op(x):
        return four_d(x, (cos, sin), jnp.float32)

    place = float(jnp.finfo(dtype).eps)
    want, vjp_want = jax.vjp(four_d, x)
    got, vjp_got = jax.vjp(
        lambda x: rope_wide(x, cos, sin, heads, interpret=True), x
    )
    assert got.dtype == x.dtype
    if rot < D:  # the trailing columns pass through untouched
        np.testing.assert_array_equal(
            _f32(got.reshape(2, t, heads, D)[..., rot:]),
            _f32(x.reshape(2, t, heads, D)[..., rot:]),
        )
    for a, b in [(got, want), (vjp_got(g)[0], vjp_want(g)[0])]:
        np.testing.assert_allclose(_f32(a), _f32(b), atol=place, rtol=place)
    np.testing.assert_allclose(
        _f32(got), _f32(by_op(x.astype(dtype))), atol=2 * place, rtol=2 * place
    )


@pytest.fixture(scope="module")
def layer():
    t = 64
    cfg = llama.LlamaConfig(
        vocab_size=64, block_size=t, n_layer=1, n_head=H, n_kv_head=HKV,
        n_embd=H * D, intermediate=64, dtype=jnp.float32,
    )
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    e, kv = H * D, HKV * D
    lp = {
        "wq": jax.random.normal(ks[0], (e, e)) * e ** -0.5 * 2.0,
        "wk": jax.random.normal(ks[1], (e, kv)) * e ** -0.5 * 2.0,
        "wv": jax.random.normal(ks[2], (e, kv)) * e ** -0.5,
        "wo": jax.random.normal(ks[3], (e, e)) * e ** -0.5,
    }
    h = jax.random.normal(ks[4], (2, t, e))
    w = jax.random.normal(ks[5], (2, t, e))
    return cfg, lp, h, w, llama.rope_table(cfg, t)


def _half_loss(attn_fn, cfg, w, rope, policy, h, lp):
    def half(h, lp):
        return llama.attention_half(h, lp, cfg, attn_fn, *rope)

    if policy:
        half = jax.checkpoint(half, policy=remat.full_policy())
    return jnp.sum(half(h, lp) * w)


def test_attention_half_wide_route_against_the_4d_route(layer):
    """One layer's loss and its gradients to h and the four matrices:
    the flash function with its keywords bound takes the wide route
    (event ``flash.wide``), with and without ``remat="full"``; the
    same function behind a plain callable the 4-D one. Float32: the
    loss to 1e-6, the gradients to 2e-5 of their largest entry
    (``delta``'s order of addition)."""
    cfg, lp, h, w, rope = layer
    kw = dict(causal=True, window=48, block_q=32, block_k=32, interpret=True)
    routes = {
        "wide": (functools.partial(flash_attention, **kw), False),
        "wide_remat_full": (functools.partial(flash_attention, **kw), True),
        "4d": (lambda q, k, v: flash_attention(q, k, v, **kw), False),
    }
    tracer = obs.configure_tracer()
    try:
        out = {}
        for name, (attn_fn, policy) in routes.items():
            before = len(tracer.events())
            out[name] = jax.jit(jax.value_and_grad(
                functools.partial(_half_loss, attn_fn, cfg, w, rope, policy),
                argnums=(0, 1),
            ))(h, lp)
            fired = [
                e for e in tracer.events()[before:]
                if e["name"] == "flash.wide"
            ]
            assert bool(fired) == (name != "4d"), (name, fired)
            assert all(
                (e["heads"], e["kv_heads"], e["head_dim"]) == (H, HKV, D)
                for e in fired
            )
    finally:
        obs.disable_tracer()
    want, g_want = out.pop("4d")
    for name, (got, g_got) in out.items():
        assert float(got) == pytest.approx(float(want), rel=1e-6), name
        for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0
            assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * scale, name


def test_under_full_remat_the_wide_route_keeps_o_where_it_lies(layer):
    """What "full" keeps of the wide route, by name: ``o`` as the
    kernel wrote it, which is the model's layout under a unit dim, the
    compact ``lse``, v as projected, and q and k rotated, as the
    kernels read them (the backward does not rotate them again)."""
    cfg, lp, h, w, rope = layer
    flash = functools.partial(flash_attention, causal=True, **BLOCKS)
    jaxpr = jax.make_jaxpr(jax.grad(
        functools.partial(_half_loss, flash, cfg, w, rope, True),
        argnums=(0, 1),
    ))(h, lp)

    def walk(jp, names, calls):
        for eqn in jp.eqns:
            if eqn.primitive.name == "name":
                names.add((eqn.params["name"], eqn.outvars[0].aval.shape))
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn.params["name"])
            for v in eqn.params.values():
                for x in v if isinstance(v, (tuple, list)) else [v]:
                    x = getattr(x, "jaxpr", x)
                    if hasattr(x, "eqns"):
                        walk(x, names, calls)
        return names, calls

    names, calls = walk(jaxpr.jaxpr, set(), [])
    t = cfg.block_size
    assert ("flash_o", (2, 1, t, H * D)) in names
    assert ("flash_lse", (2, H, 1, t)) in names
    assert ("attn_in", (2, t, H * D)) in names
    assert ("attn_in", (2, t, HKV * D)) in names
    # The forward kernel once (its kept outputs serve the backward),
    # the rotation of q and k forward and of dq and dk back, one group
    # sum for dk and dv.
    assert calls.count("flash_attention_fwd") == 1
    assert calls.count("flash_attention_bwd") == 1
    assert calls.count("rope_wide") == 4
    assert calls.count("flash_group_sum") == 1


def test_wide_entry_splits_batch_rows_and_whole_heads_over_a_mesh():
    """Under a mesh the call goes through ``shard_map``: batch rows
    over ``fsdp``, the heads over ``tensor`` along the columns, whole
    heads of q and of k and v to a device (2 query heads and 1
    key-value head each here), ``lse`` by its own heads' dim."""
    t = 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, t, H * D))
    k = jax.random.normal(ks[1], (2, t, HKV * D))
    v = jax.random.normal(ks[2], (2, t, HKV * D))

    def call(q, k, v):
        return flash_module.flash_attention_wide(
            q, k, v, n_head=H, n_kv_head=HKV, causal=True,
            return_lse=True, **BLOCKS,
        )

    want = jax.jit(call)(q, k, v)
    mesh = build_mesh(
        MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4]
    )
    with jax.set_mesh(mesh):
        lowered = jax.jit(call).lower(q, k, v)
        assert "manual_axes" in lowered.as_text()
        got = lowered.compile()(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
