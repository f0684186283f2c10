"""Per-module jaxpr profiler + roofline search prior.

Parity target: AProfiler's per-module FLOPs/latency attribution
feeding the strategy engine (atorch/utils/prof.py:39,490). The "done"
criterion from the round brief: the strategy search finds the
known-best config in fewer dry-runs when seeded by the profiler's
roofline prior than by the memory prior.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.utils.module_profiler import (
    ModuleCost,
    predict_step_time,
    profile_modules,
    total_cost,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _predict_v5e(*args, **kwargs):
    """predict_step_time for the generation these CPU tests plan for:
    off a TPU nobody gets a chip's peaks without naming it."""
    from dlrover_tpu.utils.profiler import chip_peaks

    tflops, gbps = chip_peaks(default="v5e")
    return predict_step_time(
        *args, peak_tflops=tflops, peak_hbm_gbps=gbps, **kwargs
    )


def _toy(p, x):
    with jax.named_scope("proj"):
        h = x @ p["w1"]
    with jax.named_scope("act"):
        h = jax.nn.relu(h)
    return h.sum()


class TestAttribution:
    def test_matmul_flops_exact(self):
        p = {"w1": jnp.ones((64, 32))}
        x = jnp.ones((8, 64))
        costs = profile_modules(_toy, p, x)
        # 2 * M * N * K = 2 * 8 * 32 * 64
        assert costs["proj"].flops == pytest.approx(2 * 8 * 32 * 64)

    def test_grad_roughly_doubles_matmul_flops(self):
        p = {"w1": jnp.ones((64, 32))}
        x = jnp.ones((8, 64))
        fwd = profile_modules(_toy, p, x)["proj"].flops
        both = profile_modules(_toy, p, x, grad=True)["proj"].flops
        # value_and_grad differentiates wrt params only: fwd + dW
        # (no dX matmul for a leaf input), plus small elementwise.
        assert both == pytest.approx(2 * fwd, rel=0.1)

    def test_scan_multiplies_by_length(self):
        def f(p, x):
            def body(c, _):
                with jax.named_scope("cell"):
                    return c @ p["w"], None
            h, _ = jax.lax.scan(body, x, None, length=5)
            return h.sum()

        p = {"w": jnp.ones((16, 16))}
        x = jnp.ones((4, 16))
        costs = profile_modules(f, p, x)
        assert costs["cell"].flops == pytest.approx(
            5 * 2 * 4 * 16 * 16
        )

    def test_abstract_inputs_no_execution(self):
        p = {"w1": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        costs = profile_modules(_toy, p, x)
        assert costs["proj"].flops > 0

    def test_gpt_scopes_and_ordering(self):
        from dlrover_tpu.models import gpt

        cfg = gpt.GPTConfig.nano()
        params = jax.eval_shape(
            functools.partial(gpt.init_params, cfg=cfg),
            jax.random.PRNGKey(0),
        )
        tok = jax.ShapeDtypeStruct((2, cfg.block_size), jnp.int32)
        loss = functools.partial(gpt.loss_fn, cfg=cfg)
        costs = profile_modules(
            loss, params, tok, tok, grad=True, top_level_only=True
        )
        for scope in ("head", "mlp", "attn", "embed"):
            assert scope in costs, costs.keys()
        # The scan's own scope ("layers", there for the device
        # profile's reader) moves no key: a block's cost is its
        # module's, under the name the strategy engine knows.
        assert "layers" not in costs
        nested = profile_modules(loss, params, tok, tok, grad=True)
        assert "attn" in nested and "mlp" in nested
        assert not any("layers" in k.split("/") for k in nested)
        # nano GPT: the vocab head dominates, mlp has 2x the matmul
        # volume of attention projections.
        assert costs["head"].flops > costs["mlp"].flops
        assert costs["mlp"].flops > costs["attn"].flops
        # Nothing substantial left unattributed.
        total = total_cost(costs)
        assert costs.get("<root>", ModuleCost()).flops < (
            0.05 * total.flops
        )


class TestRooflinePrior:
    def _strategies(self):
        from dlrover_tpu.accelerate.strategy import Strategy

        mesh = (("data", 1),)
        return (
            Strategy(mesh, remat="none", micro_batch_size=2),
            Strategy(mesh, remat="full", micro_batch_size=2),
        )

    def test_remat_costs_flops(self):
        per_sample = ModuleCost(flops=1e12, bytes=1e9)
        none_s, full_s = self._strategies()
        t_none = _predict_v5e(per_sample, none_s, 1)
        t_full = _predict_v5e(per_sample, full_s, 1)
        assert t_full > t_none  # recompute is not free

    def test_dtype_costs_bandwidth(self):
        import dataclasses

        # Bandwidth-bound regime: few FLOPs, lots of traffic.
        per_sample = ModuleCost(flops=1e9, bytes=1e12)
        none_s, _ = self._strategies()
        f32_s = dataclasses.replace(none_s, dtype="float32")
        assert _predict_v5e(
            per_sample, f32_s, 1
        ) > _predict_v5e(per_sample, none_s, 1)

    def test_pipe_bubble_costs_time(self):
        """Without a comm model, a pipe mesh must rank BELOW the
        equivalent fsdp mesh — the 1F1B bubble is pure overhead."""
        from dlrover_tpu.accelerate.strategy import Strategy

        per_sample = ModuleCost(flops=1e12, bytes=1e9)
        fsdp = Strategy((("fsdp", 8),), remat="none",
                        micro_batch_size=4)
        pipe = Strategy((("pipe", 8),), remat="none",
                        micro_batch_size=4)
        assert _predict_v5e(
            per_sample, pipe, 8
        ) > _predict_v5e(per_sample, fsdp, 8)

    def test_deep_model_ranks_pipe_above_fsdp(self):
        """The reason pipeline is in the search space at all (ref
        optimization_library.py:38-56): a DEEP model's per-step fsdp
        param re-sync traffic dwarfs the 1F1B bubble, so with the ICI
        term the ranking flips — pipe above pure FSDP — while a small
        model keeps fsdp on top."""
        from dlrover_tpu.accelerate.strategy import Strategy

        per_sample = ModuleCost(flops=1e12, bytes=1e9)
        fsdp = Strategy((("fsdp", 8),), remat="none",
                        micro_batch_size=4)
        pipe = Strategy((("pipe", 8),), remat="none",
                        micro_batch_size=4)
        deep_params = 40 << 30  # 10B params f32 basis
        small_params = 40 << 20
        t_fsdp_deep = _predict_v5e(
            per_sample, fsdp, 8, param_bytes=deep_params
        )
        t_pipe_deep = _predict_v5e(
            per_sample, pipe, 8, param_bytes=deep_params
        )
        assert t_pipe_deep < t_fsdp_deep
        t_fsdp_small = _predict_v5e(
            per_sample, fsdp, 8, param_bytes=small_params
        )
        t_pipe_small = _predict_v5e(
            per_sample, pipe, 8, param_bytes=small_params
        )
        assert t_fsdp_small < t_pipe_small

    def test_search_finds_known_best_in_fewer_dry_runs(self):
        """The round's done-criterion, measured: when both remat
        variants fit in memory, the known-best GPT config (no remat —
        fewer FLOPs) is dry-run FIRST under the roofline prior, while
        the memory prior (lower resident bytes = better) tries the
        remat candidate first and needs one more dry-run."""
        import dataclasses as dc

        from dlrover_tpu.accelerate.analyser import (
            analyse_model,
            estimate_step_memory,
        )
        from dlrover_tpu.accelerate.api import _roofline_prior
        from dlrover_tpu.accelerate.bayes_search import (
            BayesStrategySearch,
        )
        from dlrover_tpu.models import gpt

        cfg = dc.replace(
            gpt.GPTConfig.nano(), n_layer=2, block_size=64
        )
        model_init = functools.partial(gpt.init_params, cfg=cfg)
        model_loss = functools.partial(gpt.loss_fn, cfg=cfg)
        tok = jnp.zeros((2, cfg.block_size), jnp.int32)
        candidates = list(self._strategies())
        best = candidates[0]  # no-remat: fewer FLOPs, fits easily

        roof = _roofline_prior(
            model_init, model_loss, (tok, tok), candidates, 1,
            chip="v5e",
        )
        assert roof is not None

        analysis = analyse_model(model_init)
        mem = [
            estimate_step_memory(analysis, s, 1 << 20, 16 << 30)[0]
            for s in candidates
        ]

        def dry_runs_until_best(prior):
            search = BayesStrategySearch(
                candidates, cost_prior=prior
            )
            for n in range(1, len(candidates) + 1):
                cand = search.suggest()
                if cand == best:
                    return n
                search.observe(cand, 1.0)  # any finite throughput
            return len(candidates) + 1

        n_roofline = dry_runs_until_best(roof)
        n_memory = dry_runs_until_best(mem)
        assert n_roofline == 1
        assert n_roofline < n_memory


class TestChipPeaks:
    """One peaks table, keyed by device kind; nothing is assumed."""

    @staticmethod
    def _fake_tpu(monkeypatch, kind):
        import types

        from dlrover_tpu.utils import profiler

        monkeypatch.setattr(profiler.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            profiler.jax, "devices",
            lambda: [types.SimpleNamespace(device_kind=kind)],
        )
        return profiler

    def test_off_tpu_the_caller_names_the_generation(self):
        from dlrover_tpu.utils.profiler import (
            _device_peak_tflops,
            chip_peaks,
        )

        assert chip_peaks(default="v5e") == (197.0, 819.0)
        with pytest.raises(ValueError, match="not a TPU"):
            chip_peaks()
        assert _device_peak_tflops() is None

    def test_attached_chip_wins_over_the_default(self, monkeypatch):
        profiler = self._fake_tpu(monkeypatch, "TPU v5 lite")
        assert profiler.chip_peaks(default="v4") == (197.0, 819.0)
        assert profiler._device_peak_tflops() == 197.0

    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        profiler = self._fake_tpu(monkeypatch, "TPU v9 mega")
        with pytest.raises(ValueError, match="TPU v9 mega"):
            profiler.chip_peaks(default="v5e")
        with pytest.raises(ValueError, match="TPU v9 mega"):
            profiler._device_peak_tflops()


class TestCompileCache:
    def test_enable_sets_config_and_creates_dir(self, tmp_path):
        from dlrover_tpu.common.config import cache_dir
        from dlrover_tpu.trainer.jax_env import enable_compile_cache

        old = jax.config.jax_compilation_cache_dir
        try:
            # Nothing configured: the fixed path inside the checkout.
            jax.config.update("jax_compilation_cache_dir", None)
            d = enable_compile_cache()
            assert d == cache_dir("jax")
            assert os.path.isdir(d)
            assert d.startswith(REPO_ROOT + os.sep)
            assert jax.config.jax_compilation_cache_dir == d
            # A configured cache (JAX_COMPILATION_CACHE_DIR lands in
            # the same config value) is never clobbered, only made.
            other = str(tmp_path / "other")
            jax.config.update("jax_compilation_cache_dir", other)
            assert enable_compile_cache() == other
            assert os.path.isdir(other)
            assert jax.config.jax_compilation_cache_dir == other
        finally:
            jax.config.update("jax_compilation_cache_dir", old)

    @pytest.mark.parametrize("from_env", [True, False])
    def test_cache_files_land_where_promised(self, tmp_path, from_env):
        """A fresh process that goes through setup_distributed writes
        its compiles under JAX_COMPILATION_CACHE_DIR when that is
        set, else under <checkout>/.cache/jax."""
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
            "PYTHONPATH": REPO_ROOT,
        }
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        want = os.path.join(REPO_ROOT, ".cache", "jax")
        if from_env:
            want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "c")
        # A program no other test compiles, so it is a fresh entry.
        salt = 1000 + os.getpid()
        code = (
            "import os, jax, jax.numpy as jnp\n"
            "from dlrover_tpu.trainer import jax_env\n"
            "jax_env.setup_distributed()\n"
            "d = jax.config.jax_compilation_cache_dir\n"
            "before = set(os.listdir(d))\n"
            f"jax.jit(lambda x: x * {salt} + 1)(jnp.ones(3))"
            ".block_until_ready()\n"
            "print('CACHE', d, len(set(os.listdir(d)) - before))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, text=True,
            capture_output=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        _, got_dir, n_new = out.stdout.strip().splitlines()[-1].split()
        assert got_dir == want
        assert int(n_new) >= 1

    def test_no_cache_path_is_built_elsewhere(self):
        """One function owns the cache directory: nothing else in the
        repo sets it, in code or through a child's environment."""
        offenders = []
        for root in ("dlrover_tpu", "examples", "tools"):
            for dirpath, _, files in os.walk(os.path.join(REPO_ROOT, root)):
                offenders += [
                    os.path.join(dirpath, f) for f in files
                    if f.endswith((".py", ".sh"))
                ]
        offenders += [
            os.path.join(REPO_ROOT, f) for f in ("bench.py", "chip_smoke.py")
        ]
        setters = (
            'update("jax_compilation_cache_dir"',
            "JAX_COMPILATION_CACHE_DIR=",
            '"JAX_COMPILATION_CACHE_DIR":',
            "initialize_cache(",
        )
        bad = []
        for path in offenders:
            with open(path) as f:
                text = f.read()
            if path.endswith(os.path.join("trainer", "jax_env.py")):
                continue
            bad += [(path, s) for s in setters if s in text]
        assert not bad


class TestTpPlannerPerEdgeBytes:
    def test_profiled_edge_bytes_change_the_plan(self):
        from dlrover_tpu.accelerate.tp_planner import Op, plan_chain

        # A 2-matmul chain ending in a reduce (must be R). With a huge
        # SECOND activation, ending m2 sharded and gathering is
        # expensive, so m2 should go "row" (psum) when its true output
        # bytes are known; with the uniform default both matmuls look
        # alike.
        ops = [
            Op("m1", "matmul", (256, 256)),
            Op("m2", "matmul", (256, 256),
               activation_bytes=64_000_000.0),
            Op("loss", "reduce"),
        ]
        plan = plan_chain(
            ops, tensor_size=4, activation_bytes=1000.0,
            mem_weight=8.0,
        )
        by_name = {p.name: p for p in plan}
        # m2's output is enormous: the planner must not leave it
        # sharded-then-gathered NOR psum it (comm is priced in its
        # own bytes); the cheap path is column m1 (R->S) then row m2
        # paying psum... which costs 64MB — worse than replicating
        # m2's weight (256*256*2 bytes * 8 weight) — so m2 ends
        # replicated on the S path is impossible (needs R in). The
        # exact optimum: m1 column (R->S), m2 row (S->R) would pay
        # 64e6; m1+m2 replicated pays 2*8*128KB ~ 2e6. Assert the
        # planner avoids the 64 MB move.
        assert by_name["m2"].strategy != "row"
        # And with uniform small bytes the classic megatron pairing
        # IS chosen — the override is what changed the plan.
        plan_uniform = plan_chain(
            [
                Op("m1", "matmul", (256, 256)),
                Op("m2", "matmul", (256, 256)),
                Op("loss", "reduce"),
            ],
            tensor_size=4,
            activation_bytes=1000.0,
            mem_weight=8.0,
        )
        by_name_u = {p.name: p for p in plan_uniform}
        assert by_name_u["m1"].strategy == "column"
        assert by_name_u["m2"].strategy == "row"
