"""Sharded train-step factory: model + mesh + optimizer -> pjit step.

The TPU-native core of what the reference assembles from DDP/FSDP/TP
wrappers + NCCL groups: here the entire parallelism strategy is the
(mesh, rules) pair; XLA inserts the gradient psums and weight
all-gathers. One function builds init and step for any model exposing
(init_params, param_logical_axes, loss_fn).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.parallel.mesh import under_mesh
from dlrover_tpu.parallel.sharding import (
    Rules,
    prune_specs_to_mesh,
    tree_specs,
)


def batch_spec(mesh: Mesh) -> P:
    return prune_specs_to_mesh(mesh, P(("data", "fsdp"), "seq"))


def make_sharded_init(
    mesh: Mesh,
    init_fn: Callable[[jax.Array], Any],
    logical_axes,
    optimizer: optax.GradientTransformation,
    rules: Optional[Rules] = None,
):
    """Returns init(key) -> (params, opt_state), each properly sharded
    at creation (no host-side full materialization)."""
    param_specs = prune_specs_to_mesh(
        mesh, tree_specs(logical_axes, rules)
    )
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )

    def _init(key):
        params = init_fn(key)
        return params, init_opt_state(optimizer, params)

    # Optimizer state mirrors param sharding; scalars stay replicated.
    def _out_shardings(key):
        params_shape, opt_shape = jax.eval_shape(_init, key)
        opt_shardings = _match_opt_sharding(
            opt_shape, params_shape, param_shardings, mesh
        )
        return param_shardings, opt_shardings

    def init(key):
        p_shard, o_shard = _out_shardings(key)
        return jax.jit(_init, out_shardings=(p_shard, o_shard))(key)

    return init, param_shardings


def init_opt_state(optimizer: optax.GradientTransformation, params):
    """``optimizer.init(params)`` with every float leaf in at least
    f32 (the repo's own optimizers, optim/low_bit.py, already keep
    f32 moments whatever the parameters are).

    optax creates adam's moments in the parameters' dtype and updates
    them in the gradients'. The trainers sum gradients in an f32
    accumulator, so with bf16 parameters step 1 took bf16 moments and
    returned f32 ones: step 2 saw new input dtypes and compiled the
    whole program again, 11.6 s on a v5e at GPT-2 124M at every start
    and every resume (chip run, PR 21). State that starts in f32 goes
    in as it comes out; zeros are the same in either dtype, so the
    numbers are those the trainers had anyway, and so is the memory
    they held from step 2 on."""

    def widen(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(jnp.promote_types(leaf.dtype, jnp.float32))
        return leaf

    return jax.tree.map(widen, optimizer.init(params))


def _match_opt_sharding(opt_shape, params_shape, param_shardings, mesh):
    """Give optimizer-state leaves the sharding of the param they
    mirror (matched by shape: the moments are wider than bf16
    parameters, see :func:`init_opt_state`), replicating everything
    else."""
    flat_params = jax.tree.leaves(params_shape)
    flat_shardings = jax.tree.leaves(
        param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    by_shape = {}
    for p, s in zip(flat_params, flat_shardings):
        by_shape.setdefault(p.shape, s)
    replicated = NamedSharding(mesh, P())

    def pick(leaf):
        return by_shape.get(leaf.shape, replicated)

    return jax.tree.map(pick, opt_shape)


class StateStep:
    """``jax.jit`` of ``fn(params, opt_state, *rest) -> (params,
    opt_state, out)`` whose new state keeps the old state's
    shardings, so the state one step returns is what the next one
    was compiled for.

    Left alone XLA lays an output out as it likes. Under ``fsdp``
    GPT-2's ``lnf_b`` and its moments came back split over the axis
    though the init replicates them; step 2 saw new input shardings
    and compiled the whole step again, at every start (seen on four
    v5e chips, PR 21; 19 s a compile in the sandbox). jit wants
    ``out_shardings`` when it is built, and the builders only get the
    mesh, so the jit is built at the first call (or ``lower``) from
    that call's state. Leaves that are not laid out on a mesh (a
    host-made ``optimizer.init``) are left to XLA as before."""

    def __init__(self, fn: Callable, donate_argnums=()):
        self._fn = fn
        self._donate = tuple(donate_argnums)
        self._jit = None

    def _jitted(self, params, opt_state):
        if self._jit is None:
            if any(
                isinstance(x, jax.core.Tracer)
                for x in jax.tree.leaves((params, opt_state))
            ):
                # Inside somebody else's jit (ElasticTrainer wraps an
                # external step_fn): nothing is laid out yet, and the
                # outer jit does the pinning.
                return self._fn

            def pin(x):
                sharding = getattr(x, "sharding", None)
                return (
                    sharding
                    if isinstance(sharding, NamedSharding)
                    else None
                )

            self._jit = jax.jit(
                self._fn,
                donate_argnums=self._donate,
                out_shardings=(
                    jax.tree.map(pin, params),
                    jax.tree.map(pin, opt_state),
                    None,
                ),
            )
        return self._jit

    def __call__(self, params, opt_state, *rest):
        return self._jitted(params, opt_state)(params, opt_state, *rest)

    def lower(self, params, opt_state, *rest):
        return self._jitted(params, opt_state).lower(
            params, opt_state, *rest
        )

    def _cache_size(self) -> int:
        """Programs compiled so far (obs.profiling.CompileTracker)."""
        return 0 if self._jit is None else self._jit._cache_size()


def make_train_step(
    mesh: Mesh,
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    donate: bool = True,
):
    """Build the jitted (params, opt_state, batch) -> (params,
    opt_state, metrics) step. ``loss_fn(params, tokens, targets)``.

    Gradients come back with param sharding automatically; XLA emits
    reduce-scatter/all-gather for fsdp axes and psum for data axes.
    The one thing XLA cannot partition, a Pallas kernel, splits
    itself over the mesh it is traced under (``under_mesh``).
    """
    loss_fn = under_mesh(loss_fn, mesh)

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return StateStep(step, (0, 1) if donate else ())


class _CombinedLowered:
    """``Lowered``-shaped shim for :class:`PipelinedTrainStep` so
    ``obs.profiling.step_flops`` can price the whole optimizer step
    (accum x micro + update) through the one ``lower().cost_analysis``
    call it already makes on monolithic jitted steps."""

    def __init__(self, flops: float):
        self._flops = flops

    def cost_analysis(self) -> Dict[str, float]:
        return {"flops": self._flops}


class PipelinedTrainStep:
    """Donation-clean microbatch-pipelined accumulate-then-update step.

    The monolithic accumulation step (one jit over a ``lax.scan``)
    needs the WHOLE ``[accum, batch, ...]`` input device-resident
    before dispatch — every step pays the full batch's H2D on the
    critical path, and HBM holds accum microbatches at once. This
    driver splits the step into two jitted programs and runs the
    accumulation loop on the host:

    * ``micro(params, grad_acc, loss_acc, tokens, targets)`` — one
      microbatch's gradient, pre-scaled by ``1/accum`` and folded into
      the accumulator (bitwise the same math as the scan body). The
      accumulator, loss carry AND the microbatch input buffers are
      donated each hop, so a consumed microbatch's HBM slot is freed
      the moment its gradient lands — the pipeline's steady-state
      memory is ``pipeline_depth + 1`` microbatch slots plus one
      accumulator, never the whole batch.
    * ``update(params, opt_state, grad_acc, loss_sum)`` — the
      optimizer application, donating (params, opt_state) exactly like
      ``make_train_step``.

    Because jax dispatch is asynchronous, staging microbatch ``k+1``
    (``jax.device_put`` under the step's ``NamedSharding``) is issued
    while microbatch ``k`` executes: the host runs ahead by up to
    ``pipeline_depth`` staged slots (double buffering at depth 1), so
    H2D transfer hides behind backward compute instead of serializing
    before the step.

    ``overlap=True`` composes with the PR-7 schedule: each micro
    program mean-reduces its gradients in size-bounded buckets inside
    ``shard_map`` (``parallel.compression.bucketed_psum_mean``), so
    microbatch k's reduce ALSO overlaps k+1's backward. Requires the
    pure data-parallel regime (replicated params), like every
    shard_map reduce schedule here.

    Inputs accepted by ``__call__``: host ``np.ndarray`` batches
    (``[accum * micro, ...]`` rows — staged per microbatch right
    here, the low-HBM path), pre-staged ``[accum, micro, ...]`` device
    arrays (sliced device-side, no H2D), or a flat ``[micro, ...]``
    device batch when ``accum_steps == 1`` (the ``make_train_step``
    calling convention; the caller's buffers are NOT donated on this
    passthrough). Metrics contract matches ``make_train_step``:
    ``{"loss", "grad_norm"}``.
    """

    def __init__(
        self,
        mesh: Mesh,
        loss_fn: Callable,
        optimizer,
        accum_steps: int = 1,
        pipeline_depth: int = 1,
        donate: bool = True,
        acc_dtype=None,
        overlap: bool = False,
        bucket_mb: float = 4.0,
        bits: Optional[int] = None,
        axis_name: str = "data",
        stage_fn: Optional[Callable] = None,
        on_plan: Optional[Callable] = None,
        staged_device_inputs: Optional[bool] = None,
    ):
        """``stage_fn(tokens, targets, k) -> (tok_k, tgt_k)`` stages
        microbatch ``k`` from the host batch (defaults to the
        single-process ``device_put`` under this mesh's batch spec;
        ``ElasticTrainer`` injects its multi-process-aware stager).
        ``on_plan(plan)`` is the trace-time observability hook the
        overlapped flavor calls with its bucket plan.

        ``staged_device_inputs`` pins how DEVICE-array inputs are
        read: True = always the ``[accum, micro, ...]`` staged form
        (sliced device-side, slots donated), False = always the flat
        ``[micro, ...]`` passthrough (accum must be 1; the caller's
        buffers are never donated). ``None`` infers by the leading
        dim — ambiguous only for a flat batch whose global microbatch
        is exactly ``accum``, so callers that can hit that (a
        size-1-batch dry run) should pin it."""
        if accum_steps < 1:
            raise ValueError(
                f"accum_steps must be >= 1, got {accum_steps}"
            )
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self.mesh = mesh
        loss_fn = under_mesh(loss_fn, mesh)
        self.accum_steps = int(accum_steps)
        self.pipeline_depth = int(pipeline_depth)
        self.donate = donate
        self.acc_dtype = (
            acc_dtype if acc_dtype is not None else jnp.float32
        )
        self.overlap = True if overlap else False
        self.bits = bits
        self._bspec = batch_spec(mesh)
        self._sharding = NamedSharding(mesh, self._bspec)
        self._staged_sharding = NamedSharding(
            mesh, prune_specs_to_mesh(mesh, P(None, *self._bspec))
        )
        self._stage_fn = stage_fn or self._default_stage
        self._staged_device_inputs = staged_device_inputs
        self._warmed = False
        accum = self.accum_steps
        acc_dt = self.acc_dtype

        if self.overlap:
            from dlrover_tpu.parallel.compression import (
                bucket_plan,
                bucketed_psum_mean,
            )
            from jax import shard_map

            if any(
                s > 1
                for a, s in mesh.shape.items()
                if a != axis_name
            ):
                raise ValueError(
                    "overlapped pipelined accumulation needs a pure "
                    f"data-parallel mesh; this one shards over "
                    f"{dict(mesh.shape)}"
                )
            bucket_bytes = int(bucket_mb * (1 << 20))

            def _reduced(params, tokens, targets):
                if on_plan is not None:
                    # Trace-time note (host-side, once per compile):
                    # the bucket plan is static in the param shapes.
                    on_plan(
                        bucket_plan(jax.tree.leaves(params), bucket_bytes)
                    )
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, tokens, targets
                )
                reduced = bucketed_psum_mean(
                    jax.tree.map(lambda g: g / accum, grads),
                    axis_name,
                    bucket_bytes=bucket_bytes,
                    bits=bits,
                )
                # Per-shard loss is a local mean; pmean per hop keeps
                # the carry replicated (cheap scalar collective).
                return reduced, jax.lax.pmean(loss, axis_name)

            def micro_sharded(params, grad_acc, loss_acc, tokens, targets):
                reduced, loss = _reduced(params, tokens, targets)
                grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype),
                    grad_acc,
                    reduced,
                )
                return grad_acc, loss_acc + loss

            def micro0_sharded(params, tokens, targets):
                reduced, loss = _reduced(params, tokens, targets)
                grad_acc = jax.tree.map(
                    lambda g: g.astype(acc_dt), reduced
                )
                return grad_acc, loss

            rep = P()
            micro = shard_map(
                micro_sharded,
                mesh=mesh,
                in_specs=(rep, rep, rep, self._bspec, self._bspec),
                out_specs=(rep, rep),
                check_vma=False,
            )
            micro0 = shard_map(
                micro0_sharded,
                mesh=mesh,
                in_specs=(rep, self._bspec, self._bspec),
                out_specs=(rep, rep),
                check_vma=False,
            )
        else:

            def micro(params, grad_acc, loss_acc, tokens, targets):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, tokens, targets
                )
                # Pre-scale each microbatch by 1/accum — the exact
                # math of the monolithic scan body, so parity holds
                # bitwise.
                grad_acc = jax.tree.map(
                    lambda a, g: a + (g / accum).astype(a.dtype),
                    grad_acc,
                    grads,
                )
                return grad_acc, loss_acc + loss

            def micro0(params, tokens, targets):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, tokens, targets
                )
                grad_acc = jax.tree.map(
                    lambda g: (g / accum).astype(acc_dt), grads
                )
                return grad_acc, loss

        # The FIRST microbatch initializes the accumulator (micro0) —
        # no separate zeros program whose off-mesh placement would
        # drag the carry (and with it params, via the update) off the
        # mesh every step: the carry is born on whatever device set
        # the batch sharding dictates, exactly like the monolithic
        # scan, so steady state performs zero implicit resharding
        # transfers. Two donation flavors of each program: the
        # pipeline donates the microbatch buffers it staged (frees
        # each slot as it is consumed); the accum==1 flat passthrough
        # must not donate the CALLER's batch. Only the variants a run
        # actually uses ever compile.
        self._micro_j = jax.jit(micro, donate_argnums=(1, 2, 3, 4))
        self._micro_j_keep = jax.jit(micro, donate_argnums=(1, 2))
        self._micro0_j = jax.jit(micro0, donate_argnums=(1, 2))
        self._micro0_j_keep = jax.jit(micro0)

        def update(params, opt_state, grad_acc, loss_sum):
            gnorm = optax.global_norm(grad_acc)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grad_acc, opt_state, params
                )
                params = optax.apply_updates(params, updates)
            return params, opt_state, {
                "loss": loss_sum / accum,
                "grad_norm": gnorm,
            }

        donate_argnums = (0, 1, 2, 3) if donate else (2, 3)
        self._update_j = StateStep(update, donate_argnums)

        # Device-side microbatch slice with a STATIC index: eager
        # Array.__getitem__ would stage the index as an implicit H2D
        # constant (forbidden under the zero-sync transfer guard);
        # jitting with static_argnums bakes it into the executable.
        self._slice_j = jax.jit(
            lambda t, g, k: (t[k], g[k]), static_argnums=(2,)
        )

    # -- staging -------------------------------------------------------------

    def _default_stage(self, tokens, targets, k: int):
        """Single-process host staging: microbatch ``k``'s rows,
        committed under the step's batch sharding."""
        mb = tokens.shape[0] // self.accum_steps
        sl = slice(k * mb, (k + 1) * mb)
        return (
            jax.device_put(tokens[sl], self._sharding),
            jax.device_put(targets[sl], self._sharding),
        )

    def stage_batch(self, tokens, targets):
        """Host ``[accum * micro, ...]`` batch -> staged
        ``[accum, micro, ...]`` device arrays under
        ``P(None, *batch_spec)`` — the full-batch h2d_fn for a
        device-resident input pipeline feeding this step (the driver
        then slices device-side, paying no per-step H2D at all)."""
        accum = self.accum_steps
        sharding = self._staged_sharding
        n = (tokens.shape[0] // accum) * accum
        tok = tokens[:n].reshape((accum, -1) + tokens.shape[1:])
        tgt = targets[:n].reshape((accum, -1) + targets.shape[1:])
        return (
            jax.device_put(tok, sharding),
            jax.device_put(tgt, sharding),
        )

    def _device_input_is_staged(self, tokens) -> bool:
        """The one classifier for DEVICE-array inputs (staged
        ``[accum, micro, ...]`` vs flat ``[micro, ...]``): the
        ``staged_device_inputs`` pin when set, else inferred by the
        leading dim — shared by ``_plan_input`` and ``lower`` so
        pricing can never read a batch differently than the step."""
        if self._staged_device_inputs is not None:
            return self._staged_device_inputs
        # Infer: accum > 1 requires the staged form; at accum 1 a
        # leading dim of exactly 1 reads as staged. Callers that can
        # legitimately pass a FLAT batch of size 1 pin
        # staged_device_inputs=False instead of relying on this.
        return self.accum_steps > 1 or (
            tokens.ndim >= 1 and tokens.shape[0] == 1
        )

    def _plan_input(self, tokens, targets):
        """(stage(k) callable, donate_inputs) for the input flavor."""
        accum = self.accum_steps
        if isinstance(tokens, np.ndarray):
            return (
                lambda k: self._stage_fn(tokens, targets, k),
                True,
            )
        if self._device_input_is_staged(tokens):
            if tokens.ndim < 1 or tokens.shape[0] != accum:
                raise ValueError(
                    f"pre-staged pipelined batch must lead with "
                    f"accum={accum}; got shape {tuple(tokens.shape)}"
                )
            return (
                lambda k: self._slice_j(tokens, targets, k),
                True,
            )
        if accum != 1:
            raise ValueError(
                "flat device batches need accum_steps == 1; got "
                f"accum={accum}"
            )
        # Flat [micro, ...] device batch: the make_train_step calling
        # convention — caller keeps its buffers.
        return (lambda k: (tokens, targets), False)

    # -- the step ------------------------------------------------------------

    def __call__(self, params, opt_state, tokens, targets):
        accum = self.accum_steps
        stage, donate_inputs = self._plan_input(tokens, targets)
        micro_j = self._micro_j if donate_inputs else self._micro_j_keep
        # First call per driver = the compile boundary: silence jax's
        # cosmetic "donated buffers were not usable" lowering warning
        # there (microbatch inputs have no same-shaped output to alias
        # into — donation still invalidates them eagerly, which is the
        # point). Steady state takes the no-op path.
        guard = (
            contextlib.nullcontext()
            if self._warmed
            else warnings.catch_warnings()
        )
        micro0_j = (
            self._micro0_j if donate_inputs else self._micro0_j_keep
        )
        with guard:
            if not self._warmed:
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable",
                )
            grad_acc = loss_acc = None
            ring: collections.deque = collections.deque()
            nxt = 0
            for k in range(accum):
                # Keep pipeline_depth microbatches staged AHEAD of the
                # one being consumed: dispatch is async, so these
                # device_puts run while microbatch k-1 still computes.
                while nxt < accum and len(ring) < self.pipeline_depth + 1:
                    ring.append(stage(nxt))
                    nxt += 1
                tok_k, tgt_k = ring.popleft()
                if k == 0:
                    grad_acc, loss_acc = micro0_j(params, tok_k, tgt_k)
                else:
                    grad_acc, loss_acc = micro_j(
                        params, grad_acc, loss_acc, tok_k, tgt_k
                    )
                if donate_inputs:
                    # Donation invalidates the slot where the runtime
                    # can alias it; where it can't (no same-shaped
                    # output), free explicitly — dispatch is async but
                    # the executable holds its own reference, so the
                    # slot's HBM returns the moment the microbatch
                    # finishes, deterministically on every backend.
                    if not tok_k.is_deleted():
                        tok_k.delete()
                    if not tgt_k.is_deleted():
                        tgt_k.delete()
            out = self._update_j(params, opt_state, grad_acc, loss_acc)
        self._warmed = True
        return out

    # -- profiling seams (obs.profiling CompileTracker / MfuMeter) ----------

    def _cache_size(self) -> Optional[int]:
        total = 0
        for jfn in (
            self._micro_j, self._micro_j_keep, self._micro0_j,
            self._micro0_j_keep, self._update_j,
        ):
            probe = getattr(jfn, "_cache_size", None)
            if probe is None:
                return None
            total += int(probe())
        return total

    def lower(self, params, opt_state, tokens, targets):
        """Abstract pricing of one optimizer step: accum x the micro
        program + the update program (shapes only — works on host
        batches before anything is staged, and never dispatches)."""
        accum = self.accum_steps
        if isinstance(tokens, np.ndarray):
            gmb = (tokens.shape[0] * jax.process_count()) // accum
            tok_sds = jax.ShapeDtypeStruct(
                (gmb,) + tokens.shape[1:], tokens.dtype
            )
            tgt_sds = jax.ShapeDtypeStruct(
                (gmb,) + targets.shape[1:], targets.dtype
            )
        elif self._device_input_is_staged(tokens):
            tok_sds = jax.ShapeDtypeStruct(
                tokens.shape[1:], tokens.dtype
            )
            tgt_sds = jax.ShapeDtypeStruct(
                targets.shape[1:], targets.dtype
            )
        else:
            tok_sds = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)
            tgt_sds = jax.ShapeDtypeStruct(targets.shape, targets.dtype)
        acc_dt = self.acc_dtype
        acc_sds = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, acc_dt), params
        )
        loss_sds = jax.ShapeDtypeStruct((), jnp.float32)

        def _flops(lowered) -> float:
            return float(lowered.cost_analysis().get("flops", 0.0))

        micro0_f = _flops(
            self._micro0_j.lower(params, tok_sds, tgt_sds)
        )
        micro_f = (
            _flops(
                self._micro_j.lower(
                    params, acc_sds, loss_sds, tok_sds, tgt_sds
                )
            )
            if accum > 1
            else 0.0
        )
        upd_f = _flops(
            self._update_j.lower(params, opt_state, acc_sds, loss_sds)
        )
        return _CombinedLowered(
            micro0_f + (accum - 1) * micro_f + upd_f
        )


def make_pipelined_train_step(
    mesh: Mesh,
    loss_fn: Callable,
    optimizer,
    accum_steps: int = 1,
    pipeline_depth: int = 1,
    donate: bool = True,
    acc_dtype=None,
    overlap: bool = False,
    bucket_mb: float = 4.0,
    bits: Optional[int] = None,
    stage_fn: Optional[Callable] = None,
    on_plan: Optional[Callable] = None,
    staged_device_inputs: Optional[bool] = None,
) -> PipelinedTrainStep:
    """Build the microbatch-pipelined accumulate-then-update step —
    the ``Strategy.pipeline_depth`` schedule. See
    :class:`PipelinedTrainStep`. Same call/metrics contract as
    :func:`make_train_step` (``{"loss", "grad_norm"}``)."""
    return PipelinedTrainStep(
        mesh,
        loss_fn,
        optimizer,
        accum_steps=accum_steps,
        pipeline_depth=pipeline_depth,
        donate=donate,
        acc_dtype=acc_dtype,
        overlap=overlap,
        bucket_mb=bucket_mb,
        bits=bits,
        stage_fn=stage_fn,
        on_plan=on_plan,
        staged_device_inputs=staged_device_inputs,
    )


def make_eval_step(loss_fn: Callable):
    def step(params, tokens, targets):
        return loss_fn(params, tokens, targets)

    return jax.jit(step)


def shard_batch(mesh: Mesh, tokens, targets) -> Tuple[jax.Array, jax.Array]:
    spec = batch_spec(mesh)
    sharding = NamedSharding(mesh, spec)
    return (
        jax.device_put(tokens, sharding),
        jax.device_put(targets, sharding),
    )
