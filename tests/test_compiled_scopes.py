"""``obs.profiling.compiled_scopes``: the program's description of its
own compiled step, instruction by instruction, by scope and pass.

Toy steps of every family, built by ``ElasticTrainer`` as the
benchmark builds them (``benchmark/testdata/cells``), on the CPU: the
names are the tracing's, so what holds here holds for the chip's
program. What reads the description against a device trace is
``benchmark/readers/scope_time.py`` (tests/benchmark/test_scope_time.py).
"""

import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cell_files
from benchmark.readers.scope_time import top_level
from dlrover_tpu.accelerate import Strategy, auto_accelerate
from dlrover_tpu.obs import profiling
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

TOY = os.path.join(cell_files.HERE, "testdata", "cells")
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CELLS = ("toy-gpt.steady", "toy-mistral.steady", "toy-olmoe.steady",
         "toy-granite.steady")


def _trainer(workload, mesh_shape=(("data", 1),), batch=2, micro=2):
    """(trainer, one step's arguments) of a toy cell, on one device
    unless ``mesh_shape`` says otherwise."""
    cell = cell_files.load_cell(workload, TOY)
    family = importlib.import_module(
        f"benchmark.families.{cell['config']['family']}"
    ).build(cell["config"])
    seq = family["seq_len"]
    sample = jnp.zeros((batch, seq), jnp.int32)
    res = auto_accelerate(
        family["init"], family["loss"], family["axes"], (sample, sample),
        learning_rate=1e-3,
        strategy=Strategy(mesh_shape=mesh_shape, optimizer="adamw",
                          micro_batch_size=micro),
    )
    trainer = ElasticTrainer(
        res.mesh, family["loss"], res.optimizer,
        global_batch_size=batch, micro_batch_size=micro,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    tokens = np.arange(batch * seq, dtype=np.int32).reshape(batch, seq) % 7
    return trainer, (params, opt_state, tokens, tokens)


@pytest.fixture(scope="module", params=CELLS)
def described(request):
    """(workload, description) after one step of that family."""
    trainer, args = _trainer(request.param)
    trainer.train_step(*args)
    return request.param, profiling.compiled_scopes("train_step")


def _ends(entry, *primitives):
    return entry["op_name"].rsplit("/", 1)[-1] in primitives


def test_every_product_of_a_block_has_a_layer_scope(described):
    _, desc = described
    dots = [e for e in desc.values()
            if _ends(e, "dot_general") and "layers" in e["scope"].split("/")]
    assert len(dots) >= 6
    assert {top_level(e["scope"]) for e in dots} <= {"attn", "mlp", "ssm"}
    # Outside the layers the products are the head's.
    rest = [e for e in desc.values()
            if _ends(e, "dot_general") and "layers" not in e["scope"].split("/")]
    assert rest and {top_level(e["scope"]) for e in rest} == {"head"}


def test_the_stacks_own_slicing_is_the_layers(described):
    workload, desc = described
    # Granite's period is scanned: the scan slices the stacked
    # parameters inside its loop. The dense toy stacks are two layers
    # deep and both run in line (models/layers.py): the forward's
    # static slices of the stacked parameters, and in the backward the
    # pads that lay a layer's weight gradients into the stacked
    # leaf's rows and the sums of those.
    if workload == "toy-granite.steady":
        ops = {r"layers\)*/while/body/(dynamic_slice|dynamic_update_slice)"}
    else:
        ops = {r"/jvp\(layers\)/slice", r"/transpose\(jvp\(layers\)\)/pad",
               r"/transpose\(jvp\(layers\)\)/add_any"}
    own = []
    for op in ops:
        found = [e for e in desc.values()
                 if re.search(rf"{op}$", e["op_name"])]
        assert found, f"nothing of the layer stack's own ends in {op}"
        own += found
    assert {e["scope"] for e in own} <= {
        "accumulate/layers", "accumulate/layers/layers"
    }
    assert {top_level(e["scope"]) for e in own} == {"layers"}
    # The microbatch scan's own: the gradients' scaling into the
    # accumulator (one microbatch here, so XLA drops the while itself).
    scaled = [e for e in desc.values() if re.search(
        r"accumulate/while/body/(closed_call/)?div$", e["op_name"]
    )]
    assert scaled and {e["scope"] for e in scaled} == {"accumulate"}


def test_backward_and_recompute_are_told_apart_under_remat(described):
    _, desc = described
    by_pass = {}
    for e in desc.values():
        # (A reduction's own little computation carries a short name,
        # "attn/reduce_max", and is never timed: whole stacks only.)
        if (top_level(e["scope"]) in ("attn", "mlp", "ssm")
                and e["op_name"].startswith("jit(train_step)/")):
            by_pass.setdefault(e["pass"], []).append(e)
    assert set(by_pass) == {"fwd", "bwd", "recompute"}
    for e in by_pass["recompute"]:
        assert "rematted_computation" in e["op_name"]
        assert "transpose(" in e["op_name"]  # inside the backward
    for e in by_pass["bwd"]:
        assert "transpose(" in e["op_name"]
        assert "rematted_computation" not in e["op_name"]
    for e in by_pass["fwd"]:
        assert "transpose(" not in e["op_name"]


def test_the_heads_products_are_forward(described):
    """The head forms its gradients in the forward rule
    (ops/cross_entropy.py): all of its products are ``fwd``."""
    _, desc = described
    products = [e for e in desc.values()
                if top_level(e["scope"]) == "head" and _ends(e, "dot_general")]
    assert len(products) >= 3
    assert {e["pass"] for e in products} == {"fwd"}


def test_families_own_scopes(described):
    workload, desc = described
    paths = {e["scope"] for e in desc.values()}
    assert "optimizer" in paths and "accumulate/embed" in paths
    if "olmoe" in workload:
        for inner in ("moe_route", "moe_experts", "moe_combine"):
            assert f"accumulate/layers/mlp/{inner}" in paths
    if "granite" in workload:
        inner = {p.rsplit("/", 1)[-1] for p in paths if "/ssm/" in p}
        assert inner == {"ssm_conv", "ssd", "ssm_norm"}
        # The inner scan of a run of layers stands inside the period's.
        assert "accumulate/layers/layers/ssm" in paths
    # Every instruction is there, named or not.
    assert all(set(e) == {"scope", "pass", "op_name"} for e in desc.values())
    assert any(e["op_name"] == "" for e in desc.values())


def test_scope_of_reads_a_name_stack():
    read = profiling.scope_of
    assert read("jit(train_step)/accumulate/while/body/closed_call/"
                "jvp(layers)/while/body/checkpoint/attn/dot_general") == {
        "scope": "accumulate/layers/attn", "pass": "fwd"}
    assert read("jit(train_step)/accumulate/while/body/closed_call/"
                "transpose(jvp(layers))/while/body/checkpoint/"
                "rematted_computation/mlp/moe_route/sort") == {
        "scope": "accumulate/layers/mlp/moe_route", "pass": "recompute"}
    assert read("jit(train_step)/accumulate/while/body/closed_call/"
                "transpose(jvp(layers))/while/body/dynamic_update_slice") == {
        "scope": "accumulate/layers", "pass": "bwd"}
    assert read("jit(train_step)/optimizer/jit(silu)/mul") == {
        "scope": "optimizer", "pass": "fwd"}
    assert read("") == {"scope": "", "pass": "fwd"}
    assert read("params['wte']") == {"scope": "", "pass": "fwd"}


def test_nothing_is_lowered_until_it_is_asked_and_the_newest_trainer_answers():
    lowered = []

    def on_duration(event, duration, **kw):
        if event == LOWERING:
            lowered.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first, args = _trainer("toy-gpt.steady")
        # Built and never called: nothing to describe, nothing lowered.
        before = len(lowered)
        assert profiling.compiled_scopes("train_step") is None
        params, opt_state, _ = first.train_step(*args)
        # One lowering, which the MFU meter's pricing and the dispatch
        # share, as before this reader existed; the second step lowers
        # nothing, and neither does a tracker nobody asks.
        assert len(lowered) - before == 1
        params, opt_state, _ = first.train_step(params, opt_state, *args[2:])
        assert len(lowered) - before == 1
        gpt = profiling.compiled_scopes("train_step")
        # Asked: the same signature, so JAX may serve the lowering it
        # kept; at most one more.
        assert len(lowered) - before <= 2
        assert "accumulate/layers/attn" in {e["scope"] for e in gpt.values()}

        second, args = _trainer("toy-granite.steady")
        # A new trainer's tracker takes the name at once.
        assert profiling.compiled_scopes("train_step") is None
        second.train_step(*args)
        granite = profiling.compiled_scopes("train_step")
        assert any("/ssm" in e["scope"] for e in granite.values())
        assert not any("/ssm" in e["scope"] for e in gpt.values())
        assert profiling.compiled_scopes("no_such_step") is None
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def test_a_sharded_step_is_described_from_what_jax_kept():
    """The four-chip cell's path: parameters on ``fsdp``. The signature
    carries each leaf's sharding, so the description is of the program
    that ran (its collectives are there, under the product that needs
    them) and asking lowers and compiles nothing anew."""
    events = []

    def on_duration(event, duration, **kw):
        if event == LOWERING or event.endswith("backend_compile_duration"):
            events.append(event)

    trainer, args = _trainer(
        "toy-mistral.steady", mesh_shape=(("fsdp", 4),), batch=4, micro=1
    )
    trainer.train_step(*args)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        desc = profiling.compiled_scopes("train_step")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert events == []
    shardings = {
        leaf.sharding.spec
        for leaf in jax.tree.leaves(trainer._compile_tracker.signature)
    }
    assert any("fsdp" in spec for spec in shardings)
    gathers = [e for name, e in desc.items() if name.startswith("all-gather")]
    assert gathers
    assert {top_level(e["scope"]) for e in gathers} <= {
        "attn", "mlp", "head", "embed", "layers", "accumulate"
    }
    assert {"attn", "mlp"} <= {top_level(e["scope"]) for e in gathers}


def test_the_signature_keeps_no_buffer():
    trainer, args = _trainer("toy-gpt.steady")
    trainer.train_step(*args)
    leaves = jax.tree.leaves(trainer._compile_tracker.signature)
    assert leaves and all(
        isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves
    )
