"""auto_accelerate: one call from model to optimized sharded step.

Parity with atorch's ``auto_accelerate(model, optim_func, dataset...)``
(atorch/auto/accelerate.py:401) re-shaped for JAX: the caller hands a
functional model (init/loss/logical axes) and gets back a compiled
sharded train step + matching init, either for an explicit strategy
(``load_strategy`` path, accelerate.py:248) or via dry-run search
(the engine path, accelerate.py:196-227). No gRPC engine: SPMD JAX is
single-controller, so the "rank-0 service + task loop" machinery of
auto/engine/ is unnecessary by construction.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu import obs
from dlrover_tpu.accelerate.analyser import (
    ModelAnalysis,
    analyse_model,
    estimate_step_memory,
)
from dlrover_tpu.accelerate.strategy import (
    Strategy,
    candidate_strategies,
)
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.jax_env import enable_compile_cache
from dlrover_tpu.trainer.step import (
    make_sharded_init,
    make_train_step,
    shard_batch,
)

logger = get_logger("accelerate")


def make_optimizer(
    name: str,
    learning_rate,
    warmup_steps: int = 0,
    decay_steps: int = 0,
    schedule: str = "constant",
    grad_clip_norm: float = 0.0,
):
    """Public optimizer factory: Strategy.optimizer name -> optax
    transformation (also used by example/tooling scripts that must
    rebuild a checkpoint's optimizer-state structure).

    ``schedule``: "constant" (optionally with linear ``warmup_steps``)
    or "cosine" (warmup + cosine decay over ``decay_steps``, the HF
    Trainer default the reference's AtorchTrainer inherits).
    ``grad_clip_norm`` > 0 prepends global-norm clipping.

    Checkpoint-skeleton note: a schedule changes the optimizer-state
    structure (schedule step count), so rebuild skeletons with the
    SAME schedule settings used in training — the Trainer passes its
    TrainingArguments-derived kwargs identically in train() and
    evaluate().
    """
    if grad_clip_norm < 0:
        raise ValueError(
            f"grad_clip_norm must be >= 0, got {grad_clip_norm} "
            "(a negative max_norm would flip every update's sign)"
        )
    lr = learning_rate
    if schedule == "cosine":
        if not decay_steps:
            raise ValueError("cosine schedule needs decay_steps")
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=learning_rate,
            warmup_steps=warmup_steps,
            decay_steps=decay_steps,
            end_value=0.1 * learning_rate,
        )
    elif schedule == "constant":
        if warmup_steps:
            lr = optax.linear_schedule(
                0.0, learning_rate, warmup_steps
            )
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    base = _make_optimizer(name, lr)
    if grad_clip_norm:
        return optax.chain(
            optax.clip_by_global_norm(grad_clip_norm), base
        )
    return base


def _make_optimizer(name: str, learning_rate: float):
    if name == "adamw":
        return optax.adamw(learning_rate)
    if name == "agd":
        from dlrover_tpu.optim import agd

        return agd(learning_rate)
    if name == "adam8bit":
        from dlrover_tpu.optim import adam_8bit

        return adam_8bit(learning_rate)
    if name == "adam4bit":
        from dlrover_tpu.optim import adam_4bit

        return adam_4bit(learning_rate)
    if name == "sgd":
        return optax.sgd(learning_rate)
    raise ValueError(f"unknown optimizer {name!r}")


@dataclasses.dataclass
class AccelerateResult:
    """What auto_accelerate returns (ref AutoAccelerateResult,
    accelerate.py:230): everything needed to train."""

    strategy: Strategy
    mesh: Any
    optimizer: optax.GradientTransformation
    init_fn: Callable  # key -> (params, opt_state), sharded
    step_fn: Callable  # (params, opt_state, tokens, targets) -> ...
    shard_batch_fn: Callable  # host batch -> device-sharded batch
    throughput: Optional[float] = None  # samples/s from dry-run
    search_log: Optional[List[Dict]] = None


def _seq_attention_opts(model_loss) -> Dict:
    """Read the attention preferences out of a ``cfg`` bound into the
    loss closure (the models' functools.partial convention): a
    ``use_flash_attention=True/False`` pin survives the seq-parallel
    binding instead of being overridden by 'auto', and a declared
    ``cfg.causal`` (GPTConfig/LlamaConfig field) decides the mask."""
    fn = model_loss
    while isinstance(fn, functools.partial):
        cfg = fn.keywords.get("cfg")
        if cfg is not None:
            opts: Dict = {}
            if getattr(cfg, "sliding_window", None) is not None:
                # The ring statically skips band-dead hops, the a2a
                # passes the band to its full-sequence inner kernel
                # (parallel/ring_attention.py, parallel/ulysses.py) —
                # windowed models shard over ``seq`` at banded cost.
                opts["window"] = cfg.sliding_window
            pin = getattr(cfg, "use_flash_attention", None)
            if pin is not None:
                opts["impl"] = "flash" if pin else "xla"
            causal = getattr(cfg, "causal", None)
            if causal is not None:
                opts["causal"] = causal
            return opts
        fn = fn.func
    return {}


def _maybe_bind_seq_attention(
    model_loss,
    mesh,
    strategy: Strategy,
    seq_attention_kwargs: Optional[Dict] = None,
):
    """Honor Strategy.seq_impl: when the mesh has a real seq axis and
    the model exposes an unbound ``attn_fn`` hook (models/gpt.py,
    models/llama.py loss signatures), bind the chosen sequence-parallel
    attention family. Models without the hook (or with attn_fn already
    bound by the caller) are left alone — GSPMD sharding of the plain
    attention stays correct either way, the family knob just decides
    which collective schedule runs.

    Causality comes from ``cfg.causal`` when the model declares it
    (GPTConfig/LlamaConfig do) or from ``seq_attention_kwargs``;
    otherwise causal=True is ASSUMED and the log says so — a
    non-causal model without the declaration must either bind its own
    attn_fn (which disables this hook) or pass
    ``seq_attention_kwargs={"causal": False}``. A cfg-pinned
    ``use_flash_attention`` is honored via :func:`_seq_attention_opts`;
    explicit kwargs win over both.
    """
    import inspect

    if mesh.shape.get("seq", 1) == 1:
        return model_loss
    try:
        param = inspect.signature(model_loss).parameters.get("attn_fn")
    except (TypeError, ValueError):
        return model_loss
    if param is None:
        return model_loss
    bound_default = (
        param.default is not inspect.Parameter.empty
        and param.default is not None
    )
    if bound_default:
        # The caller already chose an attention fn — never override.
        return model_loss
    from dlrover_tpu.parallel.seq_attention import make_seq_attention

    opts = _seq_attention_opts(model_loss)
    opts.update(seq_attention_kwargs or {})
    assumed = "causal" not in opts
    attn = make_seq_attention(
        mesh, seq_impl=strategy.seq_impl, **opts
    )
    logger.info(
        "seq-parallel attention bound: seq_impl=%s opts=%s%s",
        strategy.seq_impl,
        opts,
        (
            " (causal=True ASSUMED — declare cfg.causal or pass "
            'seq_attention_kwargs={"causal": False} for a '
            "non-causal model)"
            if assumed
            else ""
        ),
    )
    return functools.partial(model_loss, attn_fn=attn)


def _build_for_strategy(
    strategy: Strategy,
    model_init: Callable,
    model_loss: Callable,
    logical_axes,
    learning_rate: float,
    devices,
    optimizer_kwargs: Optional[Dict] = None,
    seq_attention_kwargs: Optional[Dict] = None,
    pipeline_builder: Optional[Callable] = None,
):
    mesh_cfg = MeshConfig(**strategy.mesh_dict)
    n_needed = 1
    for _, s in strategy.mesh_shape:
        n_needed *= s
    if n_needed < len(devices):
        devices = devices[:n_needed]
    mesh = build_mesh(mesh_cfg, devices=devices)
    optimizer = make_optimizer(
        strategy.optimizer, learning_rate, **(optimizer_kwargs or {})
    )
    if strategy.mesh_dict.get("pipe", 1) > 1:
        # A pipe axis needs a model-supplied pipeline builder (e.g.
        # models/gpt_pipeline.GptPipelineBuilder) — the generic GSPMD
        # step cannot run 1F1B. auto_accelerate filters pipe>1
        # candidates out of the search when no builder is given, so
        # reaching here without one is caller error.
        if pipeline_builder is None:
            raise ValueError(
                f"strategy {strategy.name()} has a pipe axis but no "
                "pipeline_builder was provided"
            )
        init, step = pipeline_builder(mesh, strategy, optimizer)
        return mesh, optimizer, init, step
    init, _ = make_sharded_init(
        mesh, model_init, logical_axes, optimizer
    )
    loss = _maybe_bind_seq_attention(
        model_loss, mesh, strategy, seq_attention_kwargs
    )
    step = make_train_step(mesh, loss, optimizer)
    return mesh, optimizer, init, step


def _roofline_prior(
    model_init: Callable,
    model_loss: Callable,
    sample_batch,
    strategies: List[Strategy],
    n_devices: int,
    chip: Optional[str] = None,
) -> Optional[List[float]]:
    """Per-strategy predicted step time (lower = better) from the
    module profiler's jaxpr walk — no compilation, one abstract
    trace. None when the model cannot be traced abstractly.
    ``chip`` ranks for a NAMED target generation (utils/profiler.py
    peak tables) instead of whatever this host is — essential when
    planning for a simulated topology from a CPU CI machine. With no
    chip named the attached TPU's peaks are used, and off a TPU there
    is no prior: a ranking against peaks nobody chose is not one."""
    from dlrover_tpu.utils.profiler import (
        PEAK_HBM_GBPS,
        PEAK_TFLOPS,
        chip_generation,
    )

    chip = chip or chip_generation()
    if chip is None:
        logger.info(
            "no roofline prior: no TPU attached and no chip= named "
            "to plan for; seeding the search from the memory model"
        )
        return None
    peaks = {
        "peak_tflops": PEAK_TFLOPS[chip],
        "peak_hbm_gbps": PEAK_HBM_GBPS[chip],
    }
    try:
        from dlrover_tpu.utils.module_profiler import (
            predict_step_time,
            profile_modules,
            total_cost,
        )

        params_s = jax.eval_shape(model_init, jax.random.PRNGKey(0))
        tok, tgt = sample_batch
        one_tok = jax.ShapeDtypeStruct(
            (1,) + tuple(tok.shape[1:]), tok.dtype
        )
        one_tgt = jax.ShapeDtypeStruct(
            (1,) + tuple(tgt.shape[1:]), tgt.dtype
        )
        per_sample = total_cost(
            profile_modules(
                model_loss, params_s, one_tok, one_tgt, grad=True
            )
        )
        param_bytes = 4 * sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(params_s)
        )
        return [
            predict_step_time(
                per_sample, s, n_devices, param_bytes=param_bytes,
                **peaks,
            )
            for s in strategies
        ]
    except Exception:  # noqa: BLE001 — fall back to the memory prior
        logger.warning(
            "roofline prior unavailable; seeding search from the "
            "memory model",
            exc_info=True,
        )
        return None


def _dry_run(
    strategy: Strategy,
    built,
    sample_batch: Tuple[jax.Array, jax.Array],
    steps: int = 3,
) -> Tuple[float, float]:
    """(samples_per_sec, compile_seconds). The reference's
    dry_runner.profile — real compiled steps, timed. ``built`` is the
    (mesh, optimizer, init, step) tuple from the build cache, so the
    winning strategy's executable is reused, never recompiled."""
    mesh, _, init, step = built
    tokens, targets = sample_batch
    n = strategy.micro_batch_size
    tokens = jnp.tile(tokens[:1], (n,) + (1,) * (tokens.ndim - 1))
    targets = jnp.tile(targets[:1], (n,) + (1,) * (targets.ndim - 1))
    tokens, targets = shard_batch(mesh, tokens, targets)

    t0 = time.perf_counter()
    params, opt_state = init(jax.random.PRNGKey(0))
    out = step(params, opt_state, tokens, targets)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0

    params, opt_state, _ = out
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(
            params, opt_state, tokens, targets
        )
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    return n / dt, compile_s


def _tune_cache_key(
    analysis: ModelAnalysis, sample_batch, n_devices: int
) -> str:
    """The persistent-cache key for one search problem: model shape
    dims, per-sample batch shape/dtype, device extent, backend and
    toolchain versions (common/runmeta.trial_fingerprint). The
    per-trial *strategy* (mesh axis sizes, remat, dtype, optimizer,
    microbatch) is the trial's config, not part of the
    key — one key indexes the whole candidate space's observations."""
    from dlrover_tpu.common.runmeta import (
        package_version,
        trial_fingerprint,
    )

    tok, tgt = sample_batch
    return trial_fingerprint(
        {
            "kind": "auto_accelerate",
            "n_params": analysis.n_params,
            "largest_leaf": analysis.largest_leaf,
            # Batch dim excluded: dry-runs tile the sample to each
            # candidate's own micro batch anyway.
            "sample": [
                [list(tok.shape[1:]), str(tok.dtype)],
                [list(tgt.shape[1:]), str(tgt.dtype)],
            ],
            "n_devices": n_devices,
            "backend": jax.default_backend(),
            "jax": package_version("jax"),
            "jaxlib": package_version("jaxlib"),
        }
    )


@dataclasses.dataclass
class PlanEntry:
    """One viable strategy from plan-only analysis."""

    strategy: Strategy
    est_bytes_per_device: int
    predicted_step_s: Optional[float] = None


def plan_strategies(
    model_init: Callable[[jax.Array], Any],
    n_devices: int,
    hbm_bytes: int,
    activation_bytes_per_sample: int,
    candidates: Optional[List[Strategy]] = None,
    model_loss: Optional[Callable] = None,
    sample_batch: Optional[Tuple] = None,
    chip: Optional[str] = None,
    _analysis: Optional[ModelAnalysis] = None,
) -> List[PlanEntry]:
    """Plan-only strategy analysis: which candidates FIT a simulated
    topology, ranked — no devices, no compile, pure eval_shape (the
    reference engine's planning loop before its dry-runs,
    atorch/auto/accelerate.py:196-227). Usable in CI for topologies
    far larger than the test machine (e.g. a Llama-2-7B plan for
    v5p-32 — pass ``chip="v5p"`` so the roofline ranks with the
    TARGET generation's peaks, not this host's). With
    ``model_loss``+``sample_batch`` the ranking uses the
    module-profiler roofline (still abstract — jaxpr walk); otherwise
    the memory estimate ranks. Also the analysis core of
    :func:`auto_accelerate`'s search (single source of the memory
    gate + prior wiring).
    """
    if chip is not None:
        from dlrover_tpu.utils.profiler import PEAK_TFLOPS

        if chip not in PEAK_TFLOPS:
            # Fail fast: inside _roofline_prior a bad name would be
            # swallowed by its broad fallback and silently degrade
            # the ranking to bytes-resident.
            raise ValueError(
                f"unknown chip {chip!r}; known: "
                f"{sorted(PEAK_TFLOPS)}"
            )
    analysis = _analysis if _analysis is not None else analyse_model(
        model_init
    )
    if candidates is None:
        candidates = candidate_strategies(n_devices)
    entries: List[PlanEntry] = []
    for cand in candidates:
        est, fits = estimate_step_memory(
            analysis, cand, activation_bytes_per_sample, hbm_bytes
        )
        if fits:
            entries.append(PlanEntry(cand, est))
    if not entries:
        return []
    if model_loss is not None and sample_batch is not None:
        prior = _roofline_prior(
            model_init, model_loss, sample_batch,
            [e.strategy for e in entries], n_devices, chip=chip,
        )
        if prior is not None:
            for e, p in zip(entries, prior):
                e.predicted_step_s = p
            entries.sort(key=lambda e: e.predicted_step_s)
            return entries
    entries.sort(key=lambda e: e.est_bytes_per_device)
    return entries


def _result(strategy: Strategy, built: Tuple, **search) -> AccelerateResult:
    """What ``auto_accelerate`` hands back for ``built``
    (``_build_for_strategy``'s tuple)."""
    mesh, optimizer, init, step = built

    def init_fn(key):
        with obs.span("accel.init_state"):
            return init(key)

    TrainingMonitor.mark_phase("accelerate_done")
    return AccelerateResult(
        strategy=strategy,
        mesh=mesh,
        optimizer=optimizer,
        init_fn=init_fn,
        step_fn=step,
        shard_batch_fn=lambda t, g: shard_batch(mesh, t, g),
        **search,
    )


def auto_accelerate(
    model_init: Callable[[jax.Array], Any],
    model_loss: Callable,
    logical_axes: Any,
    sample_batch: Tuple[jax.Array, jax.Array],
    learning_rate: float = 1e-3,
    strategy: Optional[Strategy] = None,
    devices: Optional[Sequence] = None,
    candidates: Optional[List[Strategy]] = None,
    activation_bytes_per_sample: int = 1 << 20,
    hbm_bytes: Optional[int] = None,
    max_dry_runs: int = 6,
    optimizer_kwargs: Optional[Dict] = None,
    seq_attention_kwargs: Optional[Dict] = None,
    pipeline_builder: Optional[Callable] = None,
    tune_cache=None,
) -> AccelerateResult:
    """Pick (or apply) a strategy and return the compiled pieces.

    With ``strategy=`` this is the reference's load_strategy path; with
    None it analyses, prunes by memory estimate, dry-runs the top
    candidates and keeps the fastest. ``optimizer_kwargs`` forwards
    schedule/clipping knobs to make_optimizer.

    ``tune_cache``: the persistent trial cache
    (``accelerate/tune_cache.py``). ``None`` uses the env-configured
    default store (``DLROVER_TPU_TUNE_CACHE``; ``0``/``off`` disables),
    ``False`` disables for this call, a path or ``TuneCache`` selects a
    store. Matching cached observations warm-start the BO search
    (failed trials included as zero-throughput points) so a warm cache
    reaches the same winner with strictly fewer dry-runs — on TPU each
    avoided dry-run is tens of seconds of compile time — and every
    real dry-run (success or failure) is recorded back. Cache traffic
    is observable via ``dlrover_tune_cache_{hits,misses}_total``;
    replayed trials appear in ``search_log`` with ``"cached": true``.
    ``seq_attention_kwargs`` overrides the seq-parallel attention
    binding for seq-sharded strategies (e.g. ``{"causal": False}``
    for a non-causal model — the binding assumes a causal LM
    otherwise; see _maybe_bind_seq_attention).
    ``pipeline_builder(mesh, strategy, optimizer) -> (init_fn,
    step_fn)`` makes pipe>1 strategies EXECUTABLE (e.g.
    models/gpt_pipeline.GptPipelineBuilder); without one they are
    excluded from the search.
    """
    devices = list(devices if devices is not None else jax.devices())
    # The backend is up: what came before is the runtime's start.
    TrainingMonitor.mark_phase("devices_ready")
    enable_compile_cache()

    def build_strategy(s: Strategy):
        with obs.span("accel.build", strategy=s.name()):
            return _build_for_strategy(
                s, model_init, model_loss, logical_axes,
                learning_rate, devices, optimizer_kwargs,
                seq_attention_kwargs, pipeline_builder,
            )

    if strategy is not None:
        return _result(strategy, build_strategy(strategy))

    if candidates is None:
        candidates = candidate_strategies(len(devices))
    # The generic (init, loss) contract gives no stage decomposition,
    # so the GSPMD step cannot execute a pipe axis as 1F1B. With a
    # model-supplied ``pipeline_builder`` pipe candidates are real;
    # without one they stay in the GRID (plan mode / explicit
    # strategies / parallel.pipeline users see them) but out of the
    # dry-run search.
    if pipeline_builder is None:
        n_pipe = sum(
            1 for c in candidates if c.mesh_dict.get("pipe", 1) > 1
        )
        if n_pipe:
            logger.info(
                "strategy search: excluding %d pipe>1 candidates "
                "(no pipeline_builder for this model; pass one — e.g. "
                "models/gpt_pipeline.GptPipelineBuilder — to search "
                "them)",
                n_pipe,
            )
            candidates = [
                c
                for c in candidates
                if c.mesh_dict.get("pipe", 1) == 1
            ]
    hbm = hbm_bytes if hbm_bytes is not None else (16 << 30)

    # Memory gates viability; the roofline over the module profile
    # SEEDS the search (predicted step time ranks candidates far
    # better than bytes-resident, so the likely winner is dry-run
    # first and the budget shrinks). plan_strategies is the single
    # source of that gate + prior wiring (also usable standalone for
    # simulated topologies).
    with obs.span("accel.plan", candidates=len(candidates)):
        analysis = analyse_model(model_init)
        entries = plan_strategies(
            model_init, len(devices), hbm, activation_bytes_per_sample,
            candidates=candidates, model_loss=model_loss,
            sample_batch=sample_batch, _analysis=analysis,
        )
    logger.info(
        "strategy search: %d candidates, %d fit in memory",
        len(candidates),
        len(entries),
    )
    if not entries:
        raise RuntimeError(
            f"no strategy fits: model {analysis.n_params:,} params "
            f"needs more than {hbm} bytes/device on {len(devices)} "
            "devices"
        )
    viable = [e.strategy for e in entries]
    cost_prior = [
        e.predicted_step_s
        if e.predicted_step_s is not None
        else float(e.est_bytes_per_device)
        for e in entries
    ]

    # Compile cache: one build (and one XLA compile) per strategy —
    # the winner's executable is handed back, not recompiled.
    build_cache: Dict[str, Tuple] = {}

    def build(s: Strategy):
        key = s.to_json()
        if key not in build_cache:
            build_cache[key] = build_strategy(s)
        return build_cache[key]

    # BO over the viable set, seeded by the memory cost model (ref
    # bayes_opt_sg.py:35; TPU compile times make each avoided dry-run
    # tens of seconds of wall clock).
    from dlrover_tpu.accelerate.bayes_search import BayesStrategySearch

    search = BayesStrategySearch(viable, cost_prior=cost_prior)
    log: List[Dict] = []

    # Persistent trial cache: replay matching observations before any
    # dry-run is spent. Replayed points count against the budget, so
    # a warm cache converts directly into fewer compiles.
    from dlrover_tpu.accelerate import tune_cache as _tc

    cache = _tc.resolve(tune_cache)
    cache_key: Optional[str] = None
    replayed = 0
    if cache is not None:
        cache_key = _tune_cache_key(
            analysis, sample_batch, len(devices)
        )
        by_cfg: Dict[str, Dict] = {}
        for t in cache.trials(cache_key):
            if isinstance(t.get("config"), str):
                by_cfg[t["config"]] = t  # append order: newest wins
        pairs = []
        for s in viable:
            t = by_cfg.get(s.to_json())
            if t is not None:
                pairs.append(
                    (
                        s,
                        None
                        if t.get("failed")
                        else t.get("throughput"),
                    )
                )
        # A hit is a REPLAYABLE trial, not just a record for the key:
        # a Strategy schema change leaves every stored config string
        # unmatchable while the key stays identical, and that must
        # read as a miss (no work avoided), not a 100% hit rate.
        _tc.count_lookup(bool(pairs))
        replayed = search.warm_start(pairs)
        if replayed:
            for s, tput in pairs:
                entry: Dict = {"strategy": s.name(), "cached": True}
                if tput is None:
                    entry["error"] = "cached failed trial"
                else:
                    entry["samples_per_sec"] = tput
                log.append(entry)

    def run_dry_loop(search):
        fresh = 0
        while search.should_continue(max_dry_runs):
            fresh += 1
            cand = search.suggest()
            try:
                tput, compile_s = _dry_run(
                    cand, build(cand), sample_batch
                )
            except Exception as exc:  # noqa: BLE001 — OOM/shape mismatch
                logger.warning(
                    "strategy %s failed: %s", cand.name(), exc
                )
                log.append({"strategy": cand.name(), "error": str(exc)})
                search.observe(cand, None)
                if cache is not None:
                    # Failed trials are cached too: the next session's
                    # GP steers away instead of re-paying the OOM.
                    cache.record(
                        cache_key,
                        cand.to_json(),
                        None,
                        failed=True,
                        extra={"error": str(exc)[:200]},
                    )
                # the failed candidate's executables must not stay
                # resident either — they'd cascade the OOM into the
                # next dry-run
                build_cache.pop(cand.to_json(), None)
                continue
            log.append(
                {
                    "strategy": cand.name(),
                    "samples_per_sec": tput,
                    "compile_s": compile_s,
                }
            )
            logger.info(
                "dry-run %s: %.1f samples/s (compile %.1fs)",
                cand.name(),
                tput,
                compile_s,
            )
            search.observe(cand, tput)
            if cache is not None:
                cache.record(
                    cache_key,
                    cand.to_json(),
                    tput,
                    extra={"compile_s": round(compile_s, 3)},
                )
            # Evict losers' executables: keeping every dry-run program
            # resident shrinks free HBM for later candidates and can
            # fake an OOM on a strategy that fits in production.
            keep = search.best_strategy()
            keep_key = keep.to_json() if keep is not None else None
            for key in list(build_cache):
                if key != keep_key:
                    del build_cache[key]
        return fresh

    fresh_runs = run_dry_loop(search)
    chosen = search.best_strategy()
    if chosen is None and replayed and fresh_runs == 0:
        # Every observation was a replayed cached FAILURE — the budget
        # was consumed without a single fresh dry-run. Those failures
        # may be stale (a transient OOM from another process holding
        # HBM, a flaky compile), and without this retry the cache
        # would pin the job to instant permanent failure: no success
        # can ever land to clear them. Re-search from scratch with
        # fresh dry-runs; their results (either way) re-write the
        # cache.
        logger.warning(
            "warm-started search yielded no viable strategy (all %d "
            "replayed trials were cached failures); retrying with "
            "fresh dry-runs in case the failures are stale",
            replayed,
        )
        search = BayesStrategySearch(viable, cost_prior=cost_prior)
        run_dry_loop(search)
        chosen = search.best_strategy()
    if chosen is None:
        raise RuntimeError(f"all dry-runs failed: {log}")

    return _result(
        chosen, build(chosen),  # cache hit
        throughput=search.best_throughput(), search_log=log,
    )
