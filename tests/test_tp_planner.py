"""Auto TP placement planner (ref mip_tp_planner.py:1-496).

The chain DP must rediscover the Megatron pattern from first
principles (costs only), handle memory-pressure fallbacks, and emit
GSPMD-consumable PartitionSpecs that actually run on a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.accelerate.tp_planner import (
    Op,
    plan_chain,
    plan_model,
    plan_transformer_block,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


class TestChainDP:
    def test_mlp_discovers_column_then_row(self):
        """wi column + wo row = one psum, zero gathers — the Megatron
        optimum. The DP must find it from costs alone."""
        plan = plan_chain(
            [
                Op("wi", "matmul", (512, 2048)),
                Op("gelu", "elementwise"),
                Op("wo", "matmul", (2048, 512)),
            ],
            tensor_size=4,
            activation_bytes=1e6,
        )
        strategies = {p.name: p.strategy for p in plan}
        assert strategies["wi"] == "column"
        assert strategies["wo"] == "row"
        # elementwise runs on the sharded activation — no gather
        gelu = next(p for p in plan if p.name == "gelu")
        assert gelu.in_state == "S" and gelu.out_state == "S"

    def test_tiny_weights_prefer_replication(self):
        """When weights are tiny relative to activations, sharding
        buys nothing and the psum costs real bytes: replicate."""
        plan = plan_chain(
            [
                Op("w1", "matmul", (8, 8)),
                Op("w2", "matmul", (8, 8)),
            ],
            tensor_size=4,
            activation_bytes=1e9,
        )
        assert all(p.strategy == "replicated" for p in plan)

    def test_reduce_forces_gather_cost_accounting(self):
        """A reduce (loss) needs the replicated state; ending sharded
        must pay the gather, so a final row matmul (free psum exit)
        beats column+gather."""
        plan = plan_chain(
            [
                Op("wi", "matmul", (512, 2048)),
                Op("wo", "matmul", (2048, 512)),
                Op("loss", "reduce"),
            ],
            tensor_size=8,
            activation_bytes=1e6,
        )
        assert plan[-1].out_state == "R"
        strategies = {p.name: p.strategy for p in plan}
        assert strategies["wo"] == "row"

    def test_tensor_size_one_is_noop(self):
        plan = plan_chain(
            [Op("w", "matmul", (64, 64))], 1, 1e6
        )
        assert plan[0].spec == P(None, None)


class TestTransformerBlock:
    def test_block_matches_megatron_hand_rules(self):
        specs = plan_transformer_block(
            d_model=512, d_ff=2048, n_heads=8, tensor_size=4,
            batch_tokens=8192,
        )
        assert specs["wqkv"] == P(None, "tensor")
        assert specs["wo"] == P("tensor", None)
        assert specs["wi"] == P(None, "tensor")
        assert specs["wo_mlp"] == P("tensor", None)


class TestPlanModel:
    def test_fsdp_pass_bounds_memory(self):
        shapes = {
            "wi": (512, 2048),
            "wo": (2048, 512),
            "emb": (50304, 512),  # huge, not in the TP chain
        }
        chain = [
            Op("wi", "matmul", (512, 2048)),
            Op("gelu", "elementwise"),
            Op("wo", "matmul", (2048, 512)),
        ]
        # budget forces fsdp on the embedding
        specs = plan_model(
            shapes, chain, tensor_size=4, fsdp_size=8,
            batch_tokens=8192, hbm_budget_bytes=20e6,
        )
        assert specs["wi"] == P(None, "tensor")
        assert "fsdp" in tuple(specs["emb"])

    def test_unlimited_budget_leaves_non_chain_weights_alone(self):
        shapes = {"wi": (64, 256), "emb": (1000, 64)}
        chain = [Op("wi", "matmul", (64, 256))]
        specs = plan_model(shapes, chain, tensor_size=2)
        assert "emb" not in specs

    def test_planned_specs_run_on_a_real_mesh(self):
        """End to end: plan, shard, run an MLP under jit on the
        4-way tensor mesh and match the unsharded computation."""
        mesh = build_mesh(
            MeshConfig(tensor=4), devices=jax.devices()[:4]
        )
        d, ff, toks = 64, 256, 32
        key = jax.random.PRNGKey(0)
        k1, k2, kx = jax.random.split(key, 3)
        params = {
            "wi": jax.random.normal(k1, (d, ff)) * 0.1,
            "wo": jax.random.normal(k2, (ff, d)) * 0.1,
        }
        chain = [
            Op("wi", "matmul", (d, ff)),
            Op("gelu", "elementwise"),
            Op("wo", "matmul", (ff, d)),
        ]
        specs = plan_model(
            dict(wi=(d, ff), wo=(ff, d)), chain, tensor_size=4,
            batch_tokens=toks,
        )
        x = jax.random.normal(kx, (toks, d))

        def mlp(p, x):
            return jax.nn.gelu(x @ p["wi"]) @ p["wo"]

        want = mlp(params, x)
        sharded = {
            name: jax.device_put(
                arr, NamedSharding(mesh, specs[name])
            )
            for name, arr in params.items()
        }
        with jax.set_mesh(mesh):
            got = jax.jit(mlp)(sharded, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
        )
