"""Sharded train-step factory: model + mesh + optimizer -> pjit step.

The TPU-native core of what the reference assembles from DDP/FSDP/TP
wrappers + NCCL groups: here the entire parallelism strategy is the
(mesh, rules) pair; XLA inserts the gradient psums and weight
all-gathers. One function builds init and step for any model exposing
(init_params, param_logical_axes, loss_fn).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
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
