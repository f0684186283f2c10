"""Parallel fabric + model tests on the virtual 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import gpt
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.ring_attention import (
    make_sharded_attention,
    ring_attention,
)
from dlrover_tpu.parallel.sharding import (
    DEFAULT_RULES,
    prune_specs_to_mesh,
    spec_for,
    tree_specs,
)
from dlrover_tpu.trainer.step import (
    make_sharded_init,
    make_train_step,
    shard_batch,
)


class TestMesh:
    def test_resolve_wildcard(self):
        cfg = MeshConfig(data=-1, tensor=2).resolve(8)
        assert cfg.data == 4 and cfg.total == 8

    def test_build_8dev(self):
        mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        assert mesh.shape["data"] == 2
        assert mesh.shape["tensor"] == 2
        assert mesh.devices.size == 8

    def test_bad_product_raises(self):
        with pytest.raises(ValueError):
            build_mesh(MeshConfig(data=3, tensor=2))


class TestShardingRules:
    def test_spec_for(self):
        assert spec_for(("batch", "seq")) == P(("data", "fsdp"), "seq")
        assert spec_for((None, "embed")) == P(None, "fsdp")

    def test_prune(self):
        mesh = build_mesh(MeshConfig(data=8))
        spec = prune_specs_to_mesh(mesh, P(("data", "fsdp"), "tensor"))
        assert spec == P(("data",), None)


class TestRingAttention:
    def test_matches_plain_attention(self):
        mesh = build_mesh(MeshConfig(seq=8))
        b, t, h, d = 2, 64, 4, 16
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, t, h, d), jnp.float32)

        for causal in (False, True):
            ring = make_sharded_attention(mesh, causal=causal)
            got = jax.jit(ring)(q, k, v)
            want = gpt._default_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
            )

    def test_single_shard_fallback(self):
        mesh = build_mesh(MeshConfig(data=8))  # seq axis = 1
        b, t, h, d = 1, 16, 2, 8
        x = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d))
        attn = make_sharded_attention(mesh, causal=True)
        out = attn(x, x, x)
        want = gpt._default_attention(x, x, x, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5
        )


class TestWindowedSeqParallel:
    """Sliding-window attention composed with sequence sharding
    (VERDICT r4 weak #3): the windowed flash ring (static band-dead
    hop skipping), the masked XLA ring, and the banded a2a must all
    match single-device windowed attention, forward and backward."""

    def _qkv(self, b=2, t=64, h=4, d=16, seed=7):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (b, t, h, d)
        return (
            jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32),
        )

    # Windows chosen against lq=16 (t=64 over seq=4): inside one ring
    # block, exactly one block, spanning two, spanning all, and wider
    # than the sequence (degenerates to plain causal).
    @pytest.mark.parametrize("window", [5, 16, 20, 40, 100])
    @pytest.mark.parametrize("impl", ["flash", "xla"])
    def test_ring_forward_matches_plain(self, window, impl):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        ring = make_sharded_attention(
            mesh, causal=True, impl=impl, window=window
        )
        got = jax.jit(ring)(q, k, v)
        want = gpt._default_attention(
            q, k, v, causal=True, window=window
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize(
        "window",
        [5, pytest.param(20, marks=pytest.mark.slow)],
    )
    def test_ring_gradients_match_plain(self, window):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv(b=2, t=32, h=2, d=8)
        ring = make_sharded_attention(
            mesh, causal=True, impl="flash", window=window
        )

        def loss_ring(q, k, v):
            return jnp.sum(jnp.square(ring(q, k, v)))

        def loss_plain(q, k, v):
            return jnp.sum(jnp.square(gpt._default_attention(
                q, k, v, causal=True, window=window
            )))

        g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
            )

    @pytest.mark.parametrize("impl", ["flash", "xla"])
    def test_a2a_forward_matches_plain(self, impl):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        a2a = make_a2a_attention(
            mesh, causal=True, impl=impl, window=20
        )
        got = jax.jit(a2a)(q, k, v)
        want = gpt._default_attention(q, k, v, causal=True, window=20)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_seq_attention_dispatcher_passes_window(self):
        from dlrover_tpu.parallel.seq_attention import make_seq_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        for seq_impl in ("ring", "a2a", "auto"):
            attn = make_seq_attention(
                mesh, causal=True, seq_impl=seq_impl, window=12
            )
            got = jax.jit(attn)(q, k, v)
            want = gpt._default_attention(
                q, k, v, causal=True, window=12
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want),
                atol=2e-5, rtol=2e-5, err_msg=seq_impl,
            )

    def test_window_requires_causal(self):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        with pytest.raises(ValueError, match="causal"):
            make_sharded_attention(
                mesh, causal=False, impl="flash", window=8
            )

    def test_windowed_ring_truncates_hops(self):
        """The band-dead ring tail must not be traced: with window <=
        lq the flash ring unrolls to exactly one ppermute pair (t=1
        reaches back lq-1 keys; t>=2 is statically dead), and a window
        reaching back two blocks to two pairs — checked on the jaxpr,
        where the static truncation is visible without compiling."""
        from dlrover_tpu.parallel.ring_attention import (
            ring_attention_flash,
        )
        from jax import shard_map

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        spec = P(("data",), "seq", None, None)
        q, k, v = self._qkv()  # t=64 over seq=4 -> lq=16

        def count_ppermutes(window):
            fn = shard_map(
                functools.partial(
                    ring_attention_flash, causal=True, window=window
                ),
                mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
            return str(jax.make_jaxpr(fn)(q, k, v)).count("ppermute")

        assert count_ppermutes(16) == 2   # k+v, t=1 only
        assert count_ppermutes(20) == 4   # band reaches block t=2


class TestGqaRing:
    """Grouped-query attention through the sequence-parallel families
    with COMPACT K/V: the ring rotates h_kv-head tensors (1/q_per_kv
    the ppermute bytes) and broadcasts per block; the a2a exchanges
    them compact when kv heads split over the axis. Parity against
    the pre-expanded path, plus a jaxpr-level traffic assertion."""

    H, HKV = 8, 2

    def _qkv(self, b=2, t=64, d=16, seed=9):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(kq, (b, t, self.H, d), jnp.float32)
        k = jax.random.normal(kk, (b, t, self.HKV, d), jnp.float32)
        v = jax.random.normal(kv, (b, t, self.HKV, d), jnp.float32)
        return q, k, v

    def _expanded(self, k):
        return jnp.repeat(k, self.H // self.HKV, axis=2)

    @pytest.mark.parametrize("impl", ["flash", "xla"])
    @pytest.mark.parametrize("window", [None, 20])
    def test_ring_matches_expanded(self, impl, window):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        ring = make_sharded_attention(
            mesh, causal=True, impl=impl, window=window
        )
        assert ring.supports_gqa
        got = jax.jit(ring)(q, k, v)
        want = gpt._default_attention(
            q, self._expanded(k), self._expanded(v),
            causal=True, window=window,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.slow
    def test_ring_gradients_match_expanded(self):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv(t=32, d=8)
        ring = make_sharded_attention(mesh, causal=True, impl="flash")

        def loss_ring(q, k, v):
            return jnp.sum(jnp.square(ring(q, k, v)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.square(gpt._default_attention(
                q, self._expanded(k), self._expanded(v), causal=True
            )))

        g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
            )

    @pytest.mark.parametrize("seq_n", [2, 4])
    def test_a2a_matches_expanded(self, seq_n):
        """seq=2: kv heads (2) split over the axis — compact a2a
        path; seq=4: they don't — pre-broadcast fallback."""
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=seq_n, data=8 // seq_n))
        q, k, v = self._qkv(b=8 // seq_n)
        a2a = make_a2a_attention(mesh, causal=True)
        assert a2a.supports_gqa
        got = jax.jit(a2a)(q, k, v)
        want = gpt._default_attention(
            q, self._expanded(k), self._expanded(v), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_single_shard_fallback_expands(self):
        mesh = build_mesh(MeshConfig(data=8))  # seq axis = 1
        q, k, v = self._qkv(b=1, t=16, d=8)
        attn = make_sharded_attention(mesh, causal=True)
        assert attn.supports_gqa
        got = attn(q, k, v)
        want = gpt._default_attention(
            q, self._expanded(k), self._expanded(v), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_ring_rotates_compact_kv(self):
        """The whole point: ppermute operands in the jaxpr carry the
        COMPACT kv head count, not the expanded one."""
        from dlrover_tpu.parallel.ring_attention import (
            ring_attention_flash,
        )
        from jax import shard_map

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        spec = P(("data",), "seq", None, None)
        q, k, v = self._qkv()
        fn = shard_map(
            functools.partial(ring_attention_flash, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False,
        )
        txt = str(jax.make_jaxpr(fn)(q, k, v))
        ppermute_lines = [
            ln for ln in txt.splitlines() if "ppermute" in ln
        ]
        assert ppermute_lines, "no ppermute in jaxpr"
        # Every rotated tensor is [b, lq, HKV, d] per device — the
        # expanded head count (H=8) must NOT appear in any rotation.
        for ln in ppermute_lines:
            assert f",{self.HKV},"in ln.replace(" ", ""), ln
            assert f",{self.H},"not in ln.replace(" ", ""), ln


class TestRingFlashAttention:
    """Ring attention with the Pallas flash kernel per block
    (interpret mode on the CPU mesh) vs plain attention."""

    def _qkv(self, b=2, t=64, h=2, d=16):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
        shape = (b, t, h, d)
        return (
            jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32),
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_plain(self, causal):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        ring = make_sharded_attention(mesh, causal=causal, impl="flash")
        got = jax.jit(ring)(q, k, v)
        want = gpt._default_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_gradients_match_plain(self):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv(b=2, t=32, h=2, d=8)
        ring = make_sharded_attention(mesh, causal=True, impl="flash")

        def loss_ring(q, k, v):
            return jnp.sum(jnp.square(ring(q, k, v)))

        def loss_plain(q, k, v):
            return jnp.sum(
                jnp.square(gpt._default_attention(q, k, v, causal=True))
            )

        g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
            )

    def test_lse_output_and_grad(self):
        """flash_attention(return_lse=True): lse matches the naive
        logsumexp of scores and its cotangent reaches q/k."""
        from dlrover_tpu.ops.flash_attention import flash_attention

        b, t, h, d = 1, 32, 2, 8
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
        v = jax.random.normal(kv, (b, t, h, d), jnp.float32)
        _, lse = flash_attention(
            q, k, v, causal=False, interpret=True, return_lse=True
        )
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        want = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(want), atol=2e-5, rtol=2e-5
        )

        def f(q, k, v):
            _, lse = flash_attention(
                q, k, v, causal=False, interpret=True, return_lse=True
            )
            return jnp.sum(lse * jnp.arange(t, dtype=jnp.float32))

        def f_ref(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            return jnp.sum(lse * jnp.arange(t, dtype=jnp.float32))

        g1 = jax.grad(f, argnums=(0, 1))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
            )


class TestA2AAttention:
    """Ulysses-style all-to-all sequence parallelism vs plain
    attention (the second context-parallel family next to the ring;
    ref atorch distributed_attention.py:80)."""

    def _qkv(self, b=2, t=64, h=4, d=16, seed=4):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (b, t, h, d)
        return (
            jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32),
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_plain(self, causal):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()  # 4 heads % 4 seq shards == 0
        a2a = make_a2a_attention(mesh, causal=causal)
        got = jax.jit(a2a)(q, k, v)
        want = gpt._default_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_with_tensor_cosharding(self, causal):
        """heads shard over tensor FIRST; the a2a then swaps each
        tensor shard's head group (4 heads / tensor 2 = 2, % seq 2
        == 0)."""
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(data=2, seq=2, tensor=2))
        q, k, v = self._qkv()
        a2a = make_a2a_attention(mesh, causal=causal)
        got = jax.jit(a2a)(q, k, v)
        want = gpt._default_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_flash_kernel_inner(self):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv()
        a2a = make_a2a_attention(mesh, causal=True, impl="flash")
        got = jax.jit(a2a)(q, k, v)
        want = gpt._default_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_gradients_match_plain(self):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        q, k, v = self._qkv(t=32, d=8)
        a2a = make_a2a_attention(mesh, causal=True)

        def loss_a2a(q, k, v):
            return jnp.sum(jnp.square(a2a(q, k, v)))

        def loss_plain(q, k, v):
            return jnp.sum(
                jnp.square(gpt._default_attention(q, k, v, causal=True))
            )

        g1 = jax.jit(jax.grad(loss_a2a, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-4
            )

    def test_head_divisibility_guard(self):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(seq=8))
        q, k, v = self._qkv(h=4)  # 4 heads, 8 seq shards
        a2a = make_a2a_attention(mesh, causal=True)
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(a2a)(q, k, v)

    def test_single_shard_fallback(self):
        from dlrover_tpu.parallel.ulysses import make_a2a_attention

        mesh = build_mesh(MeshConfig(data=8))  # seq axis = 1
        q, k, v = self._qkv(b=1, t=16, h=2, d=8)
        a2a = make_a2a_attention(mesh, causal=True)
        want = gpt._default_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(a2a(q, k, v)), np.asarray(want),
            atol=2e-5, rtol=2e-5,
        )


class TestSeqImplDispatch:
    """make_seq_attention: the strategy-facing knob over ring vs a2a."""

    def test_choose_rules(self):
        from dlrover_tpu.parallel.seq_attention import choose_seq_impl

        assert choose_seq_impl(4, 1) == "ring"  # degenerate
        assert choose_seq_impl(8, 4) == "a2a"
        assert choose_seq_impl(6, 4) == "ring"  # 6 % 4 != 0
        # tensor co-sharding: 8/2=4 heads per tensor shard
        assert choose_seq_impl(8, 2, tensor_shards=2) == "a2a"
        assert choose_seq_impl(4, 4, tensor_shards=2) == "ring"
        assert choose_seq_impl(8, 3, tensor_shards=3) == "ring"

    @pytest.mark.parametrize("n_head", [2, 4])
    def test_auto_matches_plain_both_branches(self, n_head):
        """h=4 on seq=4 routes to a2a, h=2 to ring — both must be
        numerically plain attention."""
        from dlrover_tpu.parallel.seq_attention import make_seq_attention

        mesh = build_mesh(MeshConfig(seq=4, data=2))
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
        shape = (2, 32, n_head, 8)
        q = jax.random.normal(kq, shape, jnp.float32)
        k = jax.random.normal(kk, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        attn = make_seq_attention(mesh, causal=True)
        want = gpt._default_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(jax.jit(attn)(q, k, v)), np.asarray(want),
            atol=2e-5, rtol=2e-5,
        )

    def test_llama_gqa_composes_with_a2a(self):
        """The Llama family's grouped-query attention repeats kv heads
        to full count BEFORE attn_fn, so the a2a family's head
        constraint sees full heads — the dispatcher must route and
        match the dense forward."""
        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel.seq_attention import (
            choose_seq_impl,
            make_seq_attention,
        )

        cfg = llama.LlamaConfig(
            vocab_size=64, block_size=32, n_layer=2, n_head=4,
            n_kv_head=2, n_embd=32, intermediate=64,
            dtype=jnp.float32, remat=False,
        )
        mesh = build_mesh(MeshConfig(data=2, seq=4))
        assert choose_seq_impl(cfg.n_head, 4, 1) == "a2a"
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (2, cfg.block_size), 0,
            cfg.vocab_size,
        )
        tgt = jnp.roll(tok, -1, axis=1)
        dense = float(llama.loss_fn(params, tok, tgt, cfg=cfg))
        attn = make_seq_attention(mesh, causal=True)
        sharded = float(
            jax.jit(
                functools.partial(
                    llama.loss_fn, cfg=cfg, attn_fn=attn
                )
            )(params, tok, tgt)
        )
        np.testing.assert_allclose(sharded, dense, rtol=2e-5)

    def test_explicit_impls_and_validation(self):
        from dlrover_tpu.parallel.seq_attention import make_seq_attention

        mesh = build_mesh(MeshConfig(seq=2, data=4))
        q = jax.random.normal(jax.random.PRNGKey(8), (4, 16, 2, 8))
        for forced in ("ring", "a2a"):
            attn = make_seq_attention(mesh, causal=True, seq_impl=forced)
            want = gpt._default_attention(q, q, q, causal=True)
            np.testing.assert_allclose(
                np.asarray(jax.jit(attn)(q, q, q)), np.asarray(want),
                atol=2e-5, rtol=2e-5,
            )
        with pytest.raises(ValueError, match="seq_impl"):
            make_seq_attention(mesh, seq_impl="bogus")


def _tiny_cfg(**kw):
    base = dict(
        vocab_size=256,
        block_size=64,
        n_layer=2,
        n_head=2,
        n_embd=64,
        dtype=jnp.float32,
        remat=False,
    )
    base.update(kw)
    return gpt.GPTConfig(**base)


class TestGPT:
    def test_forward_shapes_and_finite(self):
        cfg = _tiny_cfg()
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 32), jnp.int32)
        logits = gpt.forward(params, tokens, cfg)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())

    @pytest.mark.slow
    def test_loss_decreases_single_device(self):
        cfg = _tiny_cfg()
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        opt = optax.adamw(1e-3)
        opt_state = opt.init(params)
        loss = functools.partial(gpt.loss_fn, cfg=cfg)
        step = make_train_step(
            build_mesh(MeshConfig(data=8)), loss, opt, donate=False
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size
        )
        targets = jnp.roll(tokens, -1, axis=1)
        losses = []
        for _ in range(5):
            params, opt_state, metrics = step(
                params, opt_state, tokens, targets
            )
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]

    def test_logical_axes_tree_matches_params(self):
        cfg = _tiny_cfg()
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        axes = gpt.param_logical_axes(cfg)
        jax.tree.map(
            lambda p, a: None
            if len(p.shape) == len(a)
            else pytest.fail(f"rank mismatch {p.shape} vs {a}"),
            params,
            axes,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )


class TestShardedTraining:
    @pytest.mark.parametrize(
        "mesh_cfg",
        [
            MeshConfig(data=8),
            pytest.param(
                MeshConfig(data=2, fsdp=4), marks=pytest.mark.slow
            ),
            pytest.param(
                MeshConfig(fsdp=2, tensor=4), marks=pytest.mark.slow
            ),
            pytest.param(
                MeshConfig(data=2, fsdp=2, tensor=2),
                marks=pytest.mark.slow,
            ),
        ],
        ids=["dp", "dp-fsdp", "fsdp-tp", "dp-fsdp-tp"],
    )
    def test_train_step_all_strategies(self, mesh_cfg):
        mesh = build_mesh(mesh_cfg)
        cfg = _tiny_cfg()
        opt = optax.adamw(1e-3)
        loss = functools.partial(gpt.loss_fn, cfg=cfg)
        init, shardings = make_sharded_init(
            mesh,
            functools.partial(gpt.init_params, cfg=cfg),
            gpt.param_logical_axes(cfg),
            opt,
        )
        params, opt_state = init(jax.random.PRNGKey(0))
        step = make_train_step(mesh, loss, opt)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size
        )
        tokens, targets = shard_batch(
            mesh, tokens, jnp.roll(tokens, -1, axis=1)
        )
        params, opt_state, metrics = step(
            params, opt_state, tokens, targets
        )
        assert bool(jnp.isfinite(metrics["loss"]))
        # Weights actually sharded when a weight axis is in the mesh.
        wqkv = params["blocks"]["wqkv"]
        n_shards = len({s.device for s in wqkv.addressable_shards})
        weight_ways = mesh.shape.get("fsdp", 1) * mesh.shape.get(
            "tensor", 1
        )
        if weight_ways > 1:
            assert not wqkv.sharding.is_fully_replicated
        assert n_shards == 8  # placed on every device

    def _full_against_none_on_fsdp8(self, model, make_cfg, tokens):
        """The gradient of ``model``'s loss on the 8-device ``fsdp``
        mesh under remat "full" and "none": asserts equal loss and
        gradients, returns "full"'s jaxpr."""
        from dlrover_tpu.parallel.mesh import under_mesh

        mesh = build_mesh(MeshConfig(fsdp=8))
        tokens, targets = shard_batch(
            mesh, tokens, jnp.roll(tokens, -1, axis=1)
        )
        out, jaxprs = {}, {}
        for remat in ("full", "none"):
            cfg = make_cfg(remat)
            init, _ = make_sharded_init(
                mesh,
                functools.partial(model.init_params, cfg=cfg),
                model.param_logical_axes(cfg),
                optax.adamw(1e-3),
            )
            params, _ = init(jax.random.PRNGKey(0))
            grad = jax.value_and_grad(
                under_mesh(functools.partial(model.loss_fn, cfg=cfg), mesh)
            )
            out[remat] = jax.jit(grad)(params, tokens, targets)
            jaxprs[remat] = jax.make_jaxpr(grad)(params, tokens, targets)
        np.testing.assert_allclose(
            float(out["full"][0]), float(out["none"][0]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(out["full"][1]), jax.tree.leaves(out["none"][1])
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
            )
        return jaxprs["full"]

    def test_full_remat_keeps_flash_outputs_when_sharded(self):
        """remat=True on the 8-device mesh (fsdp) with the flash
        kernel forced: the (o, lse) "full" keeps are tagged inside
        the kernel's shard_map (parallel.mesh.per_device), so
        the gradient holds the forward kernel once a layer (both
        layers run in line, models/layers.py: two calls of one block),
        each device keeps its own rows' outputs, and loss and
        gradients are remat "none"'s (tests/test_remat_policies.py
        proves the single-device structure; this proves the mesh
        path)."""
        from tests.test_remat_policies import _flash_calls

        jaxpr = self._full_against_none_on_fsdp8(
            gpt,
            lambda remat: _tiny_cfg(
                remat=remat,
                use_flash_attention=True,  # forces flash off-TPU too
                block_size=128,
                attn_blocks=(128, 128, 128, 128),
            ),
            jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0, 256),
        )
        calls = _flash_calls(jaxpr.jaxpr, [])
        assert sorted(calls) == 2 * ["flash_attention_bwd"] + 2 * [
            "flash_attention_fwd"
        ], calls

    def test_full_remat_keeps_the_expert_layers_values_when_sharded(self):
        """The same with an expert layer: the sorted path runs inside
        ``per_device``'s shard_map, each device on its own tokens, and
        what it names there is kept as on one device, by the two
        layers the stack scans (the stacked residuals) and by the
        three it runs in line (each call's results; models/layers.py):
        the one-device set, and no grouped product and no sort runs
        twice in any of the four copies of the block."""
        from dlrover_tpu.models import llama
        from tests.test_remat_policies import (
            _expert_layer_calls,
            _in_line_residuals,
            _stacked_residuals,
        )

        jaxpr = self._full_against_none_on_fsdp8(
            llama,
            lambda remat: llama.LlamaConfig(
                vocab_size=128, block_size=64, n_layer=5, n_head=4,
                n_kv_head=2, n_embd=32, intermediate=96,
                dtype=jnp.float32, remat=remat, n_experts=4,
            ),
            jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 128),
        )
        assert "shard_map" in str(jaxpr)
        assert _expert_layer_calls(jaxpr.jaxpr) == (6 * 4, 3 * 4, 2 * 4)
        # Global shapes: 8 x 64 tokens, 2 choices each, 4 experts a
        # device (a device's group sizes are its own, so [8 x 4]).
        rows, f32 = 8 * 64 * 2, "float32"
        x = ((8, 64, 32), f32)
        named = sorted([
            ((8, 64, 32), f32),                        # q
            ((8, 64, 16), f32), ((8, 64, 16), f32),    # k, v
            ((8 * 64, 4), f32),                        # router logits
            ((rows,), "int32"), ((rows,), "int32"), ((8 * 4,), "int32"),
            ((rows, 32), f32), ((rows, 32), f32),      # rows in, rows out
            ((rows, 96), f32), ((rows, 96), f32),      # up, gate
        ])
        kept = _stacked_residuals(jaxpr.jaxpr)
        assert kept == sorted(named + [x]), kept
        # In line the block's input is the call before's result, and
        # no result of the layer's own call.
        in_line = _in_line_residuals(jaxpr.jaxpr, "moe_gmm", "moe_tgmm")
        assert in_line == 3 * [named], in_line

    @pytest.mark.slow
    def test_seq_parallel_with_ring_attention(self):
        mesh = build_mesh(MeshConfig(seq=4, data=2))
        cfg = _tiny_cfg()
        attn = make_sharded_attention(mesh, causal=True)
        loss = functools.partial(gpt.loss_fn, cfg=cfg, attn_fn=attn)
        opt = optax.adamw(1e-3)
        init, _ = make_sharded_init(
            mesh,
            functools.partial(gpt.init_params, cfg=cfg),
            gpt.param_logical_axes(cfg),
            opt,
        )
        params, opt_state = init(jax.random.PRNGKey(0))
        step = make_train_step(mesh, loss, opt)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size
        )
        tokens, targets = shard_batch(
            mesh, tokens, jnp.roll(tokens, -1, axis=1)
        )
        params, opt_state, metrics = step(
            params, opt_state, tokens, targets
        )
        assert bool(jnp.isfinite(metrics["loss"]))


class TestMultiSlice:
    """Multi-slice (DCN) meshes with two virtual slices on CPU
    (ref: multi-node NCCL bootstrap, atorch distributed.py:587)."""

    def test_two_virtual_slices_outer_axis_is_slice_pure(self):
        from dlrover_tpu.parallel.mesh import (
            MeshConfig,
            build_mesh,
            mesh_slice_blocks,
        )

        devs = jax.devices()[:8]
        slice_ids = [0] * 4 + [1] * 4
        mesh = build_mesh(
            MeshConfig(data=2, fsdp=2, tensor=2, num_slices=2),
            devices=devs,
            slice_ids=slice_ids,
        )
        blocks = mesh_slice_blocks(mesh, 2)
        assert set(blocks[0]) == set(devs[:4])
        assert set(blocks[1]) == set(devs[4:])
        # the outer (data) axis blocks ARE the slices: data index 0
        # holds only slice-0 devices
        data0 = set(mesh.devices[0].flat)
        assert data0 == set(devs[:4])

    def test_interleaved_slice_ids_are_regrouped(self):
        from dlrover_tpu.parallel.mesh import group_devices_by_slice

        devs = jax.devices()[:8]
        # devices arrive interleaved across slices
        ids = [0, 1, 0, 1, 0, 1, 0, 1]
        ordered, oids = group_devices_by_slice(devs, 2, ids)
        assert oids == [0] * 4 + [1] * 4
        assert set(ordered[:4]) == {devs[0], devs[2], devs[4], devs[6]}

    def test_uneven_slices_rejected(self):
        from dlrover_tpu.parallel.mesh import group_devices_by_slice

        with pytest.raises(ValueError, match="uneven"):
            group_devices_by_slice(
                jax.devices()[:8], 2, [0, 0, 0, 0, 0, 1, 1, 1]
            )

    def test_indivisible_outer_axis_rejected(self):
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        with pytest.raises(ValueError, match="not divisible"):
            build_mesh(
                MeshConfig(data=3, tensor=2, num_slices=2),
                devices=jax.devices()[:6],
            )

    def test_train_step_runs_on_two_slice_mesh(self):
        """A real sharded computation over the 2-slice mesh: the data
        (DCN) axis psum and inner-axis collectives both execute."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(
            MeshConfig(data=2, fsdp=2, tensor=2, num_slices=2),
            devices=jax.devices()[:8],
            slice_ids=[0] * 4 + [1] * 4,
        )
        x = jnp.arange(32.0).reshape(8, 4)
        xs = jax.device_put(
            x, NamedSharding(mesh, P(("data", "fsdp"), "tensor"))
        )

        @jax.jit
        def global_mean(v):
            return jnp.mean(v)  # all-reduce across every axis incl DCN

        np.testing.assert_allclose(
            float(global_mean(xs)), float(x.mean()), rtol=1e-6
        )
