"""models/phi4_flash.py on the program's normal path, at a small size:
``remat`` "full" against "none" and the names it keeps, the memory
kept where it is made, the events and scopes of a traced loss, and
``auto_accelerate`` with ``ElasticTrainer.train_step``. (Against the
reference: tests/test_phi4_flash.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import phi4_flash as family
from dlrover_tpu.models import phi4_flash as model
from tests.test_phi4_flash import _config, _float32


def test_remat_full_keeps_by_name_and_gives_the_same_gradients():
    from dlrover_tpu import obs
    from dlrover_tpu.accelerate import remat

    cfg, params, batch = _float32(_config())
    full = dataclasses.replace(cfg, remat="full")
    grad = lambda c: jax.jit(jax.grad(
        lambda p: model.loss_fn_fused(p, *batch, cfg=c)
    ))
    tracer = obs.configure_tracer()
    try:
        kept = grad(full)(params)
        names = {
            n for e in tracer.events() if e["name"] == "remat.kept"
            for n in e["names"]
        }
    finally:
        obs.disable_tracer()
    # float32 throughout: what is kept is the value the block computed,
    # so the two differ by the order of a few sums at most (1.8e-5 of a
    # lambda vector's gradient, itself a difference of two maps' sums).
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(grad(cfg)(params))):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)
        ) + 1e-12
    assert {
        remat.SELSCAN_Y, remat.SELSCAN_STATES, remat.LAYER_MEMORY,
        remat.SHARED_KV, remat.SSM_IN, remat.ATTN_IN, remat.MLP_HIDDEN,
    } <= names
    assert names <= set(remat.KEPT)


def test_a_layer_formed_again_does_not_run_the_scan_again():
    """Under "full" the selective scan stands twice a Mamba layer in
    the compiled step, its forward (a loop over the chunks and in it
    one over a chunk's tokens) and its backward (the chunks in reverse,
    a chunk's states formed again and its cotangents), and never under
    the recomputation: its output and chunk states are kept by name,
    and the memory is kept where it is made, so its reader formed
    again does not run the producer's layer. (The convolution is
    formed again, by design.)"""
    import re

    cfg, params, batch = _float32(_config(), remat="full")
    compiled = jax.jit(jax.grad(
        lambda p: model.loss_fn_fused(p, *batch, cfg=cfg)
    )).lower(params).compile().as_text()
    loops = [
        m.group(1) for m in re.finditer(
            r'= .* while\(.*op_name="([^"]*)"', compiled
        )
    ]
    scans = [name for name in loops if "/selscan/" in name]
    mamba_layers = sum(k in (model.MAMBA, model.MEMORY) for k in cfg.kinds)
    assert len(scans) == (2 + 3) * mamba_layers == 15
    assert not [name for name in scans if "rematted_computation" in name]
    assert [name for name in loops if "rematted_computation" in name]


def test_events_and_scopes_say_what_was_traced():
    from dlrover_tpu import obs
    from dlrover_tpu.obs import profiling

    built = family.build(_config())
    cfg = built["cfg"]
    params = jax.eval_shape(built["init"], jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, cfg.block_size), jnp.int32)
    tracer = obs.configure_tracer()
    try:
        lowered = jax.jit(jax.value_and_grad(built["loss"])).lower(
            params, tok, tok
        )
        events = lambda name: [
            e for e in tracer.events() if e["name"] == name
        ]
        (pattern,) = events("sambay.pattern")
        assert pattern["kinds"] == [
            "mamba", "attn_window", "mamba", "attn_window", "mamba_memory",
            "attn_full", "gmu", "attn_cross",
        ]
        assert pattern["indices"] == list(range(2, 10))
        assert (pattern["memory_from"], pattern["memory_readers"]) == (6, [8])
        assert (pattern["kv_from"], pattern["kv_readers"]) == (7, [9])
        assert pattern["runs"] == [
            ["0_mamba__attn_window", 2], ["1_mamba_memory", 1],
            ["2_attn_full", 1], ["3_gmu__attn_cross", 1],
        ]
        (memory,) = events("gmu.memory")
        assert (memory["from_layer"], memory["readers"]) == (6, [8])
        scan = events("selscan.scan")[0]
        assert (scan["channels"], scan["states"]) == (128, 4)
        assert (scan["chunk"], scan["chunks"]) == (16, 4)
        assert scan["kept"] == ["y", "chunk_states"]
        diff = events("attn.differential")
        assert {e["window"] for e in diff} == {8, None}
        assert {
            (e["pairs"], e["kv_pairs"], e["head_dim"], e["v_width"])
            for e in diff
        } == {(4, 2, 8, 16)}
    finally:
        obs.disable_tracer()
    assert {"selscan", "gmu", "attn_diff", "attn_cross"} <= profiling.SCOPES
    text = lowered.as_text(debug_info=True)
    for scope in ("ssm/ssm_conv", "ssm/selscan", "ssm/gmu", "attn/attn_window",
                  "attn/attn_full", "attn/attn_cross",
                  "attn/attn_cross/attn_diff", "/mlp/"):
        assert scope in text, scope
    assert profiling.scope_of("jit(f)/layers/ssm/selscan/while/body/mul")[
        "scope"
    ] == "layers/ssm/selscan"


def test_normal_path_takes_steps_and_the_loss_falls():
    """auto_accelerate and ElasticTrainer.train_step on the family's
    parameter tree, two micro-batches accumulated a step: one step
    program, every loss finite (a recurrence is not believed at the
    seed's weights alone), the last under the first."""
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    built = family.build(_config())
    tok = jax.random.randint(
        jax.random.PRNGKey(4), (2, built["seq_len"] + 1), 0, built["vocab"]
    )
    tok, tgt = tok[:, :-1], tok[:, 1:]
    res = auto_accelerate(
        built["init"], built["loss"], built["axes"], (tok, tgt),
        learning_rate=3e-3,
        strategy=Strategy(
            mesh_shape=(("data", 1),), optimizer="adamw", micro_batch_size=1,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, built["loss"], res.optimizer, global_batch_size=2,
        micro_batch_size=1,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(4):
        params, opt_state, step_loss = trainer.train_step(
            params, opt_state, np.asarray(tok), np.asarray(tgt)
        )
        losses.append(float(step_loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer._compiled._cache_size() == 1
