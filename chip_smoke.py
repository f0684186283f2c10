"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # one four-chip host, that path only

With no arguments it drives the main path once, through the entry
points a user calls, at the full width of GPT-2 124M:

1. train:   ``python -m dlrover_tpu.trainer.elastic_run --standalone``
            (local master + agent + one trainer process, chip count
            found by the launcher) runs this file in its trainer role:
            ``jax_env.setup_distributed``, ``auto_accelerate``,
            ``ElasticTrainer.train_step`` fed by
            ``make_input_pipeline``, ``Checkpointer``. Six steps, a
            flash checkpoint, exit; the same command again restores in
            a fresh process and takes two more steps.
2. kernels: every Pallas kernel compiled (never interpreted) against
            its XLA reference, the main path's at their real shapes.

``--chips 4`` runs instead the path that exists only across chips:
GPT-2 124M for four steps on a ``data=4`` and on an ``fsdp=4`` mesh,
against the same batches and initial parameters on one device.

A chip belongs to one process at a time, so this parent never imports
JAX: every phase is a child with a timeout of its own, and the device
in the last line is what a child that ran on the chip read from
``jax.devices()``. Any failure — no TPU, a failed check, a child that
exits non-zero or runs out of time — is a non-zero exit with the
reason on stderr and no result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# The whole run must end inside the driver's 1200 s.
DEADLINE_S = 1150.0
_T0 = time.monotonic()

FRESH_STEPS = 6
RESUME_STEPS = 2
MULTICHIP_STEPS = 4


class SmokeFailure(Exception):
    pass


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(device: dict, chips: int) -> None:
    """The one platform check: every run the driver makes goes
    through it before any phase."""
    expect(
        device.get("platform") == "tpu",
        f"no TPU: jax.devices()[0].platform is "
        f"{device.get('platform')!r}, and this smoke run proves "
        "nothing anywhere else",
    )
    expect(
        device.get("count") == chips,
        f"asked for {chips} chip(s), JAX sees {device.get('count')}",
    )


# ---------------------------------------------------------------------------
# Parent side: children, never JAX
# ---------------------------------------------------------------------------


def run_child(name: str, cmd: list, timeout: float, env=None) -> None:
    """Run one child to its end; its output goes straight to ours.
    A non-zero exit or a timeout is a failure, and on either the
    child's whole process group is gone before this returns."""
    timeout = min(timeout, DEADLINE_S - (time.monotonic() - _T0))
    expect(timeout > 0, f"{name}: no time left inside {DEADLINE_S:.0f}s")
    t0 = time.monotonic()
    sys.stdout.flush()
    proc = subprocess.Popen(
        cmd, cwd=HERE, env=env, start_new_session=True
    )
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    wall = time.monotonic() - t0
    expect(rc is not None, f"{name}: timed out after {timeout:.0f}s")
    expect(rc == 0, f"{name}: exit code {rc} after {wall:.1f}s")
    print(f"[chip_smoke] {name}: ok in {wall:.1f}s wall", flush=True)


def child_env(work: str, **extra) -> dict:
    """A child's environment. What a job keeps per host (sockets, the
    agent's checkpoint staging, metrics, beacon, forensics) defaults
    to the temp directory, and libtpu logs under ``/tmp`` unless
    told: give both ``work``, so a run writes nowhere else and the
    clean-up takes it all."""
    return dict(
        os.environ,
        TMPDIR=work,
        TPU_LOG_DIR=os.path.join(work, "tpu_logs"),
        **extra,
    )


def run_role(role: str, work: str, size: str, timeout: float) -> dict:
    """This file again, in ``role``; returns the report it wrote."""
    report = os.path.join(work, f"{role}.json")
    run_child(
        role,
        [sys.executable, os.path.abspath(__file__), "--role", role,
         "--size", size, "--report", report],
        timeout,
        env=child_env(work),
    )
    with open(report) as f:
        return json.load(f)


def launch_trainer(name: str, work: str, size: str, timeout: float) -> dict:
    """One launch of the normal entry point, no ``--nproc_per_node``."""
    report = os.path.join(work, f"{name}.json")
    # The socket directory gets a short name of its own: AF_UNIX
    # paths end at 107 bytes.
    env = child_env(
        work,
        DLROVER_TPU_JOB_NAME=f"smoke{os.getpid()}",
        DLROVER_TPU_SOCK_DIR=os.path.join(work, "s"),
    )
    run_child(
        name,
        [sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
         "--standalone", "--max_restarts", "0",
         os.path.abspath(__file__), "--",
         "--role", "trainer", "--size", size, "--report", report,
         "--ckpt-dir", os.path.join(work, "ckpt")],
        timeout,
        env=env,
    )
    with open(report) as f:
        return json.load(f)


def phase_train(work: str, size: str, device: dict) -> None:
    """Save, exit, resume: two launches of the same command."""
    first = launch_trainer("train_launch_1", work, size, 480)
    second = launch_trainer("train_launch_2", work, size, 420)
    for tag, rep in (("launch 1", first), ("launch 2", second)):
        print(
            f"[chip_smoke] train {tag}: device {rep['device']}, "
            f"steps {rep['start_step'] + 1}..{rep['last_step']}, "
            f"losses {rep['losses']}, first step (compile included) "
            f"{rep['first_step_s']:.2f}s with {rep['step_cache_hits']} "
            f"cache hit(s) and {rep['step_cache_misses']} miss(es), "
            f"smoke observation (not a metric): "
            f"{rep['steps_per_s']:.2f} steps/s after it, "
            f"peak_bytes_in_use {rep['peak_bytes_in_use']}, "
            f"tpu_custom_call in the lowered step: "
            f"{rep['tpu_custom_calls']}, step programs compiled: "
            f"{rep['step_compiles']}, compile cache at "
            f"{rep['compile_cache_dir']}",
            flush=True,
        )
    expect(first["device"] == device and second["device"] == device,
           "the trainer saw another device than the probe")
    expect(first["start_step"] == 0, "launch 1 did not start fresh")
    expect(first["last_step"] == FRESH_STEPS, "launch 1 stopped early")
    expect(len(first["losses"]) >= FRESH_STEPS, "launch 1 logged too few")
    expect(
        second["start_step"] == first["saved_step"] == FRESH_STEPS,
        f"restored step {second['start_step']} is not the saved "
        f"step {first['saved_step']}",
    )
    expect(second["last_step"] == FRESH_STEPS + RESUME_STEPS,
           "launch 2 did not take its steps")
    losses = first["losses"] + second["losses"]
    expect(all(math.isfinite(x) for x in losses),
           f"a logged loss is not finite: {losses}")
    expect(first["losses"][-1] < first["losses"][0],
           f"the loss did not fall: {first['losses']}")
    expect(second["losses"][-1] < first["losses"][0],
           f"the resumed run lost the progress: {losses}")
    expect(
        first["step_compiles"] == second["step_compiles"] == 1,
        "a launch compiled its step more than once: "
        f"{first['step_compiles']}, {second['step_compiles']}",
    )
    if device["platform"] == "tpu":
        # The compiled kernel, not the interpreter, is in the step.
        expect(first["tpu_custom_calls"] > 0,
               "no tpu_custom_call in the train step: the flash "
               "kernel was not compiled into it")
    expect(
        second["step_cache_hits"] >= 1 and second["step_cache_misses"] == 0,
        "launch 2 compiled its step again instead of loading it: "
        f"{second['step_cache_hits']} hit(s), "
        f"{second['step_cache_misses']} miss(es)",
    )
    print(
        f"[chip_smoke] step compile cold {first['first_step_s']:.2f}s, "
        f"from the cache {second['first_step_s']:.2f}s",
        flush=True,
    )


def main_parent(chips: int) -> int:
    expect(
        os.path.isdir(os.path.join(HERE, "dlrover_tpu")),
        "chip_smoke.py drives the dlrover_tpu package of its own "
        f"checkout, and {HERE} holds none",
    )
    work = tempfile.mkdtemp(prefix="cs_")
    try:
        device = run_role("probe", work, "full", 240)["device"]
        print(f"[chip_smoke] device: {device}", flush=True)
        require_tpu(device, chips)
        if chips == 4:
            rep = run_role("multichip", work, "full", 800)
        else:
            phase_train(work, "full", device)
            rep = run_role("kernels", work, "full", 600)
        expect(rep["device"] == device,
               "the last phase saw another device than the probe")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Child side: everything below may import JAX
# ---------------------------------------------------------------------------


def describe_device() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def model_config(size: str):
    """GPT-2 124M as the repo ships it (12 layers, 768 wide, 12
    heads, block 1024, vocab 50304, bf16); "smoke" is the 2-layer
    width the CPU rehearsal can afford."""
    from dlrover_tpu.models import gpt

    if size == "full":
        return gpt.GPTConfig.gpt2()
    # bf16 like the real one, and flash forced on (interpreted off the
    # TPU), so the rehearsal takes the dtypes and the kernel's path
    # through the step and through shard_map.
    return gpt.GPTConfig(
        vocab_size=256, block_size=64, n_layer=2, n_head=2, n_embd=64,
        remat=False, use_flash_attention=True,
    )


def synthetic_tokens(n_tokens: int, vocab: int, seed: int):
    """A seeded stream with structure to learn: Zipfian unigrams with
    a deterministic bigram mixed in, so the loss falls within a few
    steps from a random init."""
    import numpy as np

    rng = np.random.default_rng(seed)
    base = rng.zipf(1.3, size=n_tokens).astype(np.int64) % vocab
    mix = rng.random(n_tokens) < 0.3
    return np.where(
        mix, (np.roll(base, 1) * 7 + 3) % vocab, base
    ).astype(np.int32)


class CacheCounter:
    """Counts XLA persistent-cache hits and misses in this process."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def trainer_main(args) -> int:
    """The script the launcher runs: what examples/nanogpt/train.py
    does, at GPT-2 124M, logging every step."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.agent.monitor import TrainingMonitor
    from dlrover_tpu.data.prefetch import make_input_pipeline
    from dlrover_tpu.models import gpt
    from dlrover_tpu.trainer import jax_env
    from dlrover_tpu.trainer.async_metrics import materialize
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticDistributedSampler,
        ElasticTrainer,
    )
    from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
        Checkpointer,
        StorageType,
    )

    TrainingMonitor.mark_phase("proc_start")
    jax_env.setup_distributed()
    cache = CacheCounter()
    cfg = model_config(args.size)
    full = args.size == "full"
    n_dev = len(jax.devices())
    batch = 18 if full else 4  # per chip

    model_init = functools.partial(gpt.init_params, cfg=cfg)
    model_loss = functools.partial(gpt.loss_fn_fused, cfg=cfg)
    sample = jnp.zeros((2, cfg.block_size), jnp.int32)
    res = auto_accelerate(
        model_init, model_loss, gpt.param_logical_axes(cfg),
        (sample, sample), learning_rate=6e-4,
        strategy=Strategy(
            mesh_shape=(("data", n_dev),),
            optimizer="adamw",
            micro_batch_size=batch,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, model_loss, res.optimizer,
        global_batch_size=batch * n_dev, micro_batch_size=batch,
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))

    ckpt = Checkpointer(args.ckpt_dir)
    start_step = 0
    restored = ckpt.load_checkpoint(
        (params, opt_state),
        shardings=jax.tree.map(lambda x: x.sharding, (params, opt_state)),
    )
    if restored is not None:
        params, opt_state = restored
        start_step = ckpt.last_restored_step
    trainer.step_num = start_step
    last_step = start_step + (RESUME_STEPS if start_step else FRESH_STEPS)

    data = synthetic_tokens(400_000, cfg.vocab_size, seed=1337)
    sampler = ElasticDistributedSampler(
        dataset_size=len(data) - cfg.block_size - 1,
        num_shards=jax_env.num_processes(),
        shard_rank=max(jax_env.process_id(), 0),
        seed=1337 + start_step,
    )
    it = iter(sampler)

    def batch_stream():
        n = trainer.local_samples_per_step
        while True:
            idx = np.fromiter((next(it) for _ in range(n)), np.int64, n)
            yield (
                np.stack([data[i: i + cfg.block_size] for i in idx]),
                np.stack([data[i + 1: i + cfg.block_size + 1] for i in idx]),
            )

    batches = make_input_pipeline(
        batch_stream(),
        h2d_fn=lambda b: trainer.shard_microbatches(*b),
        name="chip_smoke",
    )
    losses, step_ends = [], []
    try:
        for step in range(start_step + 1, last_step + 1):
            tok, tgt = next(batches)
            if step == start_step + 1:
                # The compiled kernel shows in the lowered module as a
                # tpu_custom_call; the interpreter leaves none.
                custom_calls = trainer._compiled.lower(
                    params, opt_state, tok, tgt
                ).as_text().count("tpu_custom_call")
                hits0, misses0 = cache.hits, cache.misses
                t_first = time.monotonic()
            params, opt_state, loss = trainer.train_step(
                params, opt_state, tok, tgt
            )
            losses.append(float(materialize(loss, reason="log")))
            step_ends.append(time.monotonic())
            if step == start_step + 1:
                step_hits = cache.hits - hits0
                step_misses = cache.misses - misses0
            TrainingMonitor.write_metrics(
                step,
                tokens=(step - start_step) * trainer.samples_per_step
                * cfg.block_size,
            )
            print(f"step {step}: loss {losses[-1]:.4f}", flush=True)
    finally:
        batches.close()
    ckpt.save_checkpoint(
        last_step, (params, opt_state), storage_type=StorageType.DISK
    )
    ckpt.wait_latest_checkpoint()
    ckpt.close()
    stats = jax.local_devices()[0].memory_stats() or {}
    report = {
        "device": describe_device(),
        "start_step": start_step,
        "last_step": last_step,
        "saved_step": last_step,
        "losses": [round(x, 4) for x in losses],
        "first_step_s": step_ends[0] - t_first,
        "step_cache_hits": step_hits,
        "step_cache_misses": step_misses,
        "steps_per_s": (len(step_ends) - 1)
        / max(step_ends[-1] - step_ends[0], 1e-9),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "tpu_custom_calls": custom_calls,
        "step_compiles": trainer._compiled._cache_size(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    with open(args.report, "w") as f:
        json.dump(report, f)
    return 0


def _norm_err(got, want) -> float:
    """max|got - want| over max|want|, in f32."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def run_kernels(size: str) -> list:
    """Each main-path kernel against its XLA reference at the shapes
    training runs it at, then every variant
    tools/tpu_kernel_smoke.py knows. Returns the parity table; a miss
    raises."""
    import functools

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.gpt import _default_attention as dense
    from dlrover_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_rect,
    )

    full = size == "full"
    table = []

    def flash_case(name, b, t, h, d, window=None):
        q, k, v = (
            jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
            for kk in jax.random.split(jax.random.PRNGKey(t + d), 3)
        )

        def objective(attn, *qkv):
            out = attn(*qkv)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        def grad(attn):
            return jax.jit(jax.value_and_grad(
                functools.partial(objective, attn), argnums=(0, 1, 2),
                has_aux=True,
            ))

        (_, out), grads = grad(functools.partial(
            flash_attention, causal=True, window=window
        ))(q, k, v)
        # The reference: the repo's dense attention on the same bf16
        # values, computed in exact f32.
        with jax.default_matmul_precision("highest"):
            (_, ref), ref_grads = grad(functools.partial(
                dense, causal=True, window=window
            ))(*(x.astype(jnp.float32) for x in (q, k, v)))
        errs = {
            "out": _norm_err(out, ref),
            **{n: _norm_err(g, r)
               for n, g, r in zip(("dq", "dk", "dv"), grads, ref_grads)},
        }
        table.append({"kernel": name, "shape": [b, t, h, d], **errs})
        print(f"[chip_smoke] parity {name} {(b, t, h, d)}: {errs}",
              flush=True)
        expect(all(e < 5e-2 for e in errs.values()),
               f"{name}: parity miss {errs}")

    if full:
        flash_case("flash_fwd_bwd", 18, 1024, 12, 64)
        # The (2, 4096, 32, 128) shape cut to 8 of its 64 (batch,
        # head) slices, over which the kernel only loops: the dense
        # reference and its backward hold several [B, H, T, T] f32
        # arrays, 64 MiB a slice each.
        flash_case("flash_fwd_bwd", 1, 4096, 8, 128)
        flash_case("flash_window1024_fwd_bwd", 1, 4096, 8, 128, 1024)
        # From 8k the backward needs more scoped VMEM than Mosaic's
        # default and declares it (ops/flash_attention.py
        # _bwd_vmem_limit): run what it declares, at the two lengths
        # whose dense reference (a GiB for each [T, T] f32 array at
        # 16k) still fits beside it.
        flash_case("flash_fwd_bwd_8k", 1, 8192, 2, 128)
        flash_case("flash_window1024_fwd_bwd_8k", 1, 8192, 2, 128, 1024)
        flash_case("flash_fwd_bwd_16k", 1, 16384, 1, 64)
        tq, tk, h, d = 512, 4096, 8, 128
    else:
        flash_case("flash_fwd_bwd", 2, 128, 2, 64)
        flash_case("flash_window32_fwd_bwd", 2, 128, 2, 64, 32)
        tq, tk, h, d = 32, 128, 2, 64
    # Rectangular: the last Tq queries against all Tk keys (chunked
    # prefill), which equals the tail rows of the square call.
    q, k, v = (
        jax.random.normal(kk, (1, tk, h, d), jnp.bfloat16)
        for kk in jax.random.split(jax.random.PRNGKey(7), 3)
    )
    rect = jax.jit(functools.partial(flash_attention_rect, causal=True))(
        q[:, -tq:], k, v
    )
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(dense, causal=True))(
            *(x.astype(jnp.float32) for x in (q, k, v))
        )[:, -tq:]
    err = _norm_err(rect, ref)
    table.append({"kernel": "flash_rect_fwd", "shape": [tq, tk, h, d],
                  "out": err})
    print(f"[chip_smoke] parity flash_rect_fwd Tq={tq} Tk={tk}: {err}",
          flush=True)
    expect(err < 5e-2, f"flash_rect_fwd: parity miss {err}")

    sys.path.insert(0, os.path.join(HERE, "tools"))
    import tpu_kernel_smoke

    results = tpu_kernel_smoke.run(small=not full)
    failed = [r for r in results if not r["ok"]]
    expect(not failed, f"kernel checks failed: {failed}")
    return table + results


def kernels_role(size: str) -> dict:
    from dlrover_tpu.parallel.mesh import use_interpret

    expect(not use_interpret(), "on a TPU the kernels must compile")
    return {"parity": run_kernels(size)}


def run_multichip(size: str, n: int = 4) -> dict:
    """The same seeded batches and initial parameters through the
    train step on one device, on ``data=n`` and on ``fsdp=n``."""
    import functools

    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models import gpt
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.step import (
        make_sharded_init,
        make_train_step,
        shard_batch,
    )

    devices = jax.devices()
    expect(len(devices) >= n, f"need {n} devices, have {len(devices)}")
    cfg = model_config(size)
    global_batch = 32 if size == "full" else 8
    data = synthetic_tokens(
        MULTICHIP_STEPS * global_batch * (cfg.block_size + 1),
        cfg.vocab_size, seed=4,
    ).reshape(MULTICHIP_STEPS, global_batch, cfg.block_size + 1)
    loss_fn = functools.partial(gpt.loss_fn_fused, cfg=cfg)
    optimizer = optax.adamw(6e-4)

    def train(axis: str, n_dev: int) -> dict:
        mesh = build_mesh(
            MeshConfig(**{axis: n_dev}), devices=devices[:n_dev]
        )
        init, _ = make_sharded_init(
            mesh, functools.partial(gpt.init_params, cfg=cfg),
            gpt.param_logical_axes(cfg), optimizer,
        )
        params, opt_state = init(jax.random.PRNGKey(0))
        step = make_train_step(mesh, loss_fn, optimizer)
        losses = []
        t0 = time.monotonic()
        for rows in data:
            tok, tgt = shard_batch(mesh, rows[:, :-1], rows[:, 1:])
            params, opt_state, metrics = step(params, opt_state, tok, tgt)
            losses.append(float(metrics["loss"]))
        wall = time.monotonic() - t0
        wqkv = params["blocks"]["wqkv"]
        stats = [d.memory_stats() for d in devices[:n_dev]]
        out = {
            "mesh": f"{axis}={n_dev}",
            "losses": losses,
            "wall_s": round(wall, 2),
            # 1 unless step 1's outputs came back laid out otherwise
            # than the init's and step 2 compiled the program again.
            "step_compiles": step._cache_size(),
            "batch_devices": len(tok.sharding.device_set),
            "param_devices": len(wqkv.sharding.device_set),
            "param_replicated": wqkv.sharding.is_fully_replicated,
            "bytes_in_use": (
                [s["bytes_in_use"] for s in stats] if all(stats) else None
            ),
        }
        # Free this run's arrays before the next layout is built.
        for leaf in jax.tree.leaves((params, opt_state, tok, tgt)):
            leaf.delete()
        print(f"[chip_smoke] multichip {out}", flush=True)
        expect(out["step_compiles"] == 1,
               f"{out['mesh']}: the step compiled "
               f"{out['step_compiles']} times: what a step returns "
               "is not laid out as what it took")
        return out

    one = train("data", 1)
    runs = {"one": one}
    for axis in ("data", "fsdp"):
        run = runs[axis] = train(axis, n)
        expect(run["batch_devices"] == n,
               f"{run['mesh']}: the batch sits on "
               f"{run['batch_devices']} device(s)")
        expect(run["param_devices"] == n,
               f"{run['mesh']}: the parameters sit on "
               f"{run['param_devices']} device(s)")
        if axis == "fsdp":
            expect(not run["param_replicated"],
                   "fsdp: the parameters are replicated, not sharded")
        used = run["bytes_in_use"]
        # The CPU backend reports no memory; a TPU always does.
        expect(used is not None or devices[0].platform != "tpu",
               "the TPU reported no memory_stats")
        if used is not None:
            expect(max(used) <= 2 * min(used),
                   f"{run['mesh']}: memory is not balanced: {used}")
        diffs = [abs(a - b) for a, b in zip(run["losses"], one["losses"])]
        print(f"[chip_smoke] {run['mesh']} vs one device: "
              f"|loss diff| per step {diffs}", flush=True)
        expect(all(np.isfinite(run["losses"])),
               f"{run['mesh']}: a loss is not finite")
        expect(
            all(d <= 1e-2 * abs(b) for d, b in zip(diffs, one["losses"])),
            f"{run['mesh']}: losses {run['losses']} leave the "
            f"one-device run's {one['losses']} by more than 1%",
        )
    return runs


# role -> (chips it requires, None for any device; what it reports)
CHILD_ROLES = {
    "probe": (None, lambda size: {}),
    "kernels": (1, kernels_role),
    "multichip": (4, lambda size: {"runs": run_multichip(size)}),
}


def child_main(args) -> int:
    chips, role = CHILD_ROLES[args.role]
    device = describe_device()
    if chips is not None:
        require_tpu(device, chips)
    with open(args.report, "w") as f:
        json.dump({"device": device, **role(args.size)}, f)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the four-chip path and what it is "
                   "compared with")
    # The arguments below are how this file re-enters itself as a
    # child; a run starts without them.
    p.add_argument("--role", default="parent",
                   choices=("parent", "trainer", *CHILD_ROLES),
                   help=argparse.SUPPRESS)
    p.add_argument("--size", default="full", choices=("full", "smoke"),
                   help=argparse.SUPPRESS)
    p.add_argument("--report", default="", help=argparse.SUPPRESS)
    p.add_argument("--ckpt-dir", default="", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    try:
        if args.role == "parent":
            return main_parent(args.chips)
        if args.role == "trainer":
            return trainer_main(args)
        return child_main(args)
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
