"""Measure AGD-vs-AdamW convergence on the nanoGPT task (real TPU).

The reference claims AGD converges up to 1.5x faster than AdamW on
nanoGPT pretraining (BASELINE.md; /root/reference/atorch/docs/
README-AGD.md:29). This runs both optimizers on identical data and
init for N steps of the bench model (GPT-2 124M unless --small) and
reports loss-at-step plus steps-to-target ratios, writing
AGD_CONVERGENCE_r05.json.

Run:  python tools/agd_convergence.py [--small] [--steps N]
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

import _repo_path  # noqa: F401

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
import jax.numpy as jnp
import optax

from dlrover_tpu.models import gpt
from dlrover_tpu.optim.agd import agd as agd_opt
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.step import (
    make_sharded_init,
    make_train_step,
    shard_batch,
)


def run(optimizer, cfg, mesh, steps, log_every):
    """Train from the same seed; return the loss trace."""
    loss = functools.partial(gpt.loss_fn_fused, cfg=cfg)
    init, _ = make_sharded_init(
        mesh,
        functools.partial(gpt.init_params, cfg=cfg),
        gpt.param_logical_axes(cfg),
        optimizer,
    )
    params, opt_state = init(jax.random.PRNGKey(0))
    step = make_train_step(mesh, loss, optimizer)

    # FRESH synthetic batch per step (same generative rule, stepped
    # seed): convergence on a data distribution, not single-batch
    # memorization — the regime the reference's 1.5x claim is about.
    # The rule (segment transforms of a shared base) is learnable, so
    # the loss trace separates optimizers, unlike uniform-random
    # tokens whose floor is log(V) for every optimizer.
    def batch(i):
        key = jax.random.PRNGKey(1000 + i)
        base = jax.random.randint(
            key, (8 * max(1, len(jax.devices())), cfg.block_size // 4),
            0, cfg.vocab_size // 4,
        )
        tokens = jnp.concatenate(
            [base, base * 2 % cfg.vocab_size,
             base * 3 % cfg.vocab_size, (base + 7) % cfg.vocab_size],
            axis=1,
        )
        targets = jnp.roll(tokens, -1, axis=1)
        return shard_batch(mesh, tokens, targets)

    trace = []
    for i in range(steps):
        tokens, targets = batch(i)
        params, opt_state, m = step(params, opt_state, tokens, targets)
        # The final step is ALWAYS logged — ratios and "final loss"
        # must describe step `steps`, not the last log_every multiple.
        if (i + 1) % log_every == 0 or (i + 1) == steps:
            trace.append((i + 1, float(m["loss"])))
    return trace


def best_finite_trace(runs):
    """(key, trace) with the lowest FINITE final loss; a NaN-diverged
    run must never win (NaN compares false against everything, which
    would freeze min() on whichever trace it met first). Falls back
    to the raw dict only if every run diverged."""
    import math

    finite = {
        k: tr for k, tr in runs.items() if math.isfinite(tr[-1][1])
    }
    return min((finite or runs).items(), key=lambda kv: kv[1][-1][1])


def steps_to(trace, target):
    for s, l in trace:
        if l <= target:
            return s
    return None


def main() -> int:
    small = "--small" in sys.argv
    steps = 200
    for i, a in enumerate(sys.argv):
        if a == "--steps":
            steps = int(sys.argv[i + 1])
    cfg = gpt.GPTConfig.gpt2() if not small else gpt.GPTConfig.nano()
    if small:
        # Reduced-scale dims, overridable (AGD_LAYERS/BLOCK/VOCAB env)
        # so the CPU fallback can run a mid-size study instead of the
        # 2-layer nano default when wall-clock allows.
        cfg = dataclasses.replace(
            cfg,
            n_layer=int(os.environ.get("AGD_LAYERS", 2)),
            block_size=int(os.environ.get("AGD_BLOCK", 128)),
            vocab_size=int(os.environ.get("AGD_VOCAB", 1024)),
            dtype=jnp.float32, remat=False,
        )
    mesh = build_mesh(MeshConfig(data=len(jax.devices())))
    log_every = max(1, steps // 40)

    t0 = time.time()

    # Both optimizers run the standard nanoGPT-style schedule —
    # linear warmup (10% of steps) + cosine to 10% of peak. This
    # matters most for AGD: with the reference-recommended
    # delta=1e-14 the early preconditioned steps are enormous while
    # v_t is still tiny, and a constant LR lets that noise dominate a
    # short study (measured r5: constant-LR AGD lost 0.86x even at
    # the reference's own lr/delta settings).
    def sched(peak):
        # warmup < decay_steps or optax's cosine segment is empty
        # (a --steps 1 smoke run would crash at construction).
        warmup = min(max(1, steps // 10), max(steps - 1, 0))
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=peak,
            warmup_steps=warmup,
            decay_steps=steps, end_value=peak * 0.1,
        )

    adamw = run(
        optax.adamw(sched(3e-4), b1=0.9, b2=0.95, weight_decay=0.1),
        cfg, mesh, steps, log_every,
    )
    # AGD at the reference's documented transformer settings — lr
    # 1/10 of AdamW's, delta 1e-14 (README-AGD.md:22-23; the first r5
    # run used AdamW's own lr and the 1e-5 delta default and AGD
    # unsurprisingly lost, speedup 0.6). Two LR points; ratios use
    # the better trace, both are recorded.
    agd_runs = {}
    for lr in (3e-5, 6e-5):
        agd_runs[lr] = run(
            agd_opt(sched(lr), betas=(0.9, 0.95), delta=1e-14,
                    weight_decay=0.1),
            cfg, mesh, steps, log_every,
        )
        # Completed-trace checkpoint: a failure during a later
        # run must not erase finished ones (the r5 longctx lesson).
        with open("/tmp/agd_partial.json", "w") as f:
            json.dump(
                {"adamw": adamw,
                 "agd": {str(k): v for k, v in agd_runs.items()}},
                f,
            )
    agd_lr, agd = best_finite_trace(agd_runs)
    # Ratio: AdamW steps / AGD steps to reach the loss AGD ends at
    # (and a mid target), >1 means AGD is faster.
    final_agd = agd[-1][1]
    mid = (agd[0][1] + final_agd) / 2
    ratios = {}
    for name, tgt in (("final_agd_loss", final_agd), ("mid_loss", mid)):
        sa, sb = steps_to(adamw, tgt), steps_to(agd, tgt)
        ratios[name] = {
            "target": round(tgt, 4),
            "adamw_steps": sa,
            "agd_steps": sb,
            "speedup": (round(sa / sb, 3) if sa and sb else None),
        }
        if sa is None and sb is not None:
            # AdamW never reached AGD's loss within the budget: the
            # true speedup is censored at steps/sb — report the floor
            # rather than an ambiguous null.
            ratios[name]["speedup_floor"] = round(steps / sb, 3)
            ratios[name]["agd_strictly_better"] = True
    out = {
        "model": (
            "gpt2-124M" if not small else
            f"nano-small(L{cfg.n_layer},T{cfg.block_size},"
            f"V{cfg.vocab_size})"
        ),
        "steps": steps,
        "backend": jax.default_backend(),
        "adamw_trace": adamw,
        "agd_lr": agd_lr,
        "adamw_lr": 3e-4,
        "agd_delta": 1e-14,
        "schedule": "warmup 10% + cosine to 0.1x peak (both)",
        "agd_trace": agd,
        "agd_traces_by_lr": {str(k): v for k, v in agd_runs.items()},
        "ratios": ratios,
        "reference_claim": "AGD up to 1.5x faster than AdamW "
                           "(atorch/docs/README-AGD.md:29)",
        "elapsed_s": round(time.time() - t0, 1),
    }
    # Same artifact gating as the other round tools: only a full-size
    # run on the real chip writes the round record. VERDICT r4 next #3
    # sanctioned fallback: if no chip can be had all round,
    # AGD_ALLOW_CPU=1 lets a reduced-scale CPU run write the round
    # artifact — loudly labeled, never silently passed off as
    # hardware-scale.
    on_tpu = jax.default_backend() == "tpu"
    cpu_fallback = (
        os.environ.get("AGD_ALLOW_CPU") == "1" and not on_tpu
    )
    if cpu_fallback:
        out["note"] = (
            "CPU fallback at reduced scale (no TPU available "
            "all round) — convergence-ratio evidence only; absolute "
            "wall-clock numbers are not hardware-representative"
        )
        out["scale"] = "reduced-cpu"
    path = (
        "AGD_CONVERGENCE_r05.json" if (on_tpu and not small) or cpu_fallback
        else "/tmp/agd_convergence_check.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(
        {"final_adamw": adamw[-1][1], "final_agd": final_agd,
         "ratios": ratios}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
