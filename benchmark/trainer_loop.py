"""The benchmark's trainer script: the program's normal training path,
timed from outside it.

What examples/nanogpt/train.py and chip_smoke.py's trainer role do
(``jax_env.setup_distributed``, ``auto_accelerate``,
``ElasticTrainer.train_step`` fed by ``make_input_pipeline``,
``Checkpointer``), for whatever cell the spec names. It runs either
inside ``run.py``'s own process (traffic kind ``steady``) or as the
script ``elastic_run --standalone`` spawns and the agent restarts
(kind ``save_kill_resume``); the calls into the program are the same.

The program is asked for nothing but itself: the spans here are put
around the calls into it (``next(batches)``, ``train_step``, the save,
the loss read), the phase marks are ``TrainingMonitor.mark_phase`` as
the program's own example places them, and step completions are taken
one step late (step i-1's loss is read after step i is dispatched), so
the host never drains the device queue to take a timestamp.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

SPAN_DATA = "bench.next_batch"
SPAN_STEP = "bench.train_step"
SPAN_LOSS = "bench.read_loss"
SPAN_SAVE = "bench.save_checkpoint"


class NotTheCell(Exception):
    """The machine is not what the cell asks for: no result."""


class CacheCounter:
    """XLA persistent-cache hits and misses, and backend compiles, in
    this process (chip_smoke.py's counter, copied)."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def total(self) -> int:
        """Every sign of a compilation: a backend compile, or a look
        in the persistent cache (which only a new program makes)."""
        return self.hits + self.misses + self.compiles

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def describe_device() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def _param_checksum_fn():
    """Bitwise checksum of a parameter tree, on the device: every
    leaf's bits summed as uint32, modulo 2**32. Exact and independent
    of the order of the sum, so equal parameters give equal numbers
    and one flipped bit does not."""
    import jax
    import jax.numpy as jnp

    def checksum(params):
        total = jnp.zeros((), jnp.uint32)
        for leaf in jax.tree.leaves(params):
            bits = jnp.uint16 if leaf.dtype.itemsize == 2 else jnp.uint32
            total = total + jnp.sum(
                jax.lax.bitcast_convert_type(leaf, bits).astype(jnp.uint32),
                dtype=jnp.uint32,
            )
        return total

    return jax.jit(checksum)


def step_hbm_bytes(trainer, params, opt_state, tok, tgt) -> dict:
    """``memory_analysis()`` of the trainer's compiled step: arguments
    plus temporaries on one device (``peak_bytes_in_use`` leaves the
    temporaries out on this backend; PERF.md, PR 21 run 4). Lowering
    and compiling again is served from the compile cache."""
    try:
        mem = trainer._compiled.lower(
            params, opt_state, tok, tgt
        ).compile().memory_analysis()
        return {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
    except Exception as exc:  # noqa: BLE001 - a reading, not the run
        print(f"[bench] no memory_analysis: {exc!r}", file=sys.stderr)
        return {}


def train(spec: dict) -> dict:
    """Run the cell's training loop; returns the report (and, when the
    spec names files, appends step records to them as they complete)."""
    t_proc = time.time()
    sys.path.insert(0, REPO)
    from dlrover_tpu.agent.monitor import TrainingMonitor

    TrainingMonitor.mark_phase("proc_start")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import cell as cell_files
    from benchmark import traffic_gen
    from benchmark.kinds import common
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.data.prefetch import make_input_pipeline
    from dlrover_tpu.trainer import jax_env
    from dlrover_tpu.trainer.async_metrics import materialize
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticDistributedSampler,
        ElasticTrainer,
    )

    jax_env.setup_distributed()
    cache = CacheCounter()
    TrainingMonitor.mark_phase("dist_ready")

    cell = spec["cell"]
    traffic, workload = cell["traffic"], cell["workload"]
    device = describe_device()
    if not spec.get("allow_cpu"):
        if device["platform"] != "tpu":
            raise NotTheCell(
                f"no TPU: jax.devices()[0].platform is "
                f"{device['platform']!r}"
            )
        if device["count"] != cell["chips"]:
            raise NotTheCell(
                f"the cell asks for {cell['chips']} chip(s), JAX sees "
                f"{device['count']}"
            )
    n_dev = len(jax.devices())
    launched = bool(spec.get("steps_file"))
    seed = int(spec["seed"])

    family = importlib.import_module(
        f"benchmark.families.{cell['config']['family']}"
    ).build(cell["config"])
    seq_len = family["seq_len"]
    micro = int(workload["micro_batch_per_chip"])
    sample = jnp.zeros((2, seq_len), jnp.int32)
    res = auto_accelerate(
        family["init"], family["loss"], family["axes"], (sample, sample),
        learning_rate=float(traffic["learning_rate"]),
        strategy=Strategy(
            mesh_shape=cell_files.mesh_shape(traffic, n_dev),
            optimizer="adamw",
            micro_batch_size=micro,
        ),
    )
    trainer = ElasticTrainer(
        res.mesh, family["loss"], res.optimizer,
        global_batch_size=micro * n_dev, micro_batch_size=micro,
    )
    # Weights and optimizer state from the seed, on the device, in one
    # jitted call, already laid out on the mesh.
    params, opt_state = res.init_fn(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    TrainingMonitor.mark_phase("built")

    ckpt = None
    start_step = 0
    restored_checksum = None
    checksum = _param_checksum_fn()
    if launched:
        from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
            Checkpointer,
            StorageType,
        )

        ckpt = Checkpointer(spec["ckpt_dir"])
        restored = ckpt.load_checkpoint(
            (params, opt_state),
            shardings=jax.tree.map(lambda x: x.sharding, (params, opt_state)),
        )
        if restored is not None:
            params, opt_state = restored
            start_step = ckpt.last_restored_step
            restored_checksum = int(checksum(params))
        TrainingMonitor.mark_phase("restore_done")
    trainer.step_num = start_step
    resumed = start_step > 0

    data = traffic_gen.token_stream(traffic["stream"], family["vocab"], seed)
    reference = None
    if not resumed:
        # The system's loss against the plain reference, one seeded
        # sequence at a time at the cell's widths, outside the window.
        # The system reads a sequence once on every chip (its batch is
        # split over the mesh), the reference once.
        from dlrover_tpu.parallel.mesh import under_mesh
        from dlrover_tpu.trainer.step import shard_batch

        t0 = time.time()
        system_loss = jax.jit(under_mesh(family["loss"], res.mesh))
        tok_h, tgt_h = traffic_gen.reference_batch(
            data, seq_len, int(traffic["reference_sequences"])
        )
        got, want = [], []
        for r in range(tok_h.shape[0]):
            row = tok_h[r: r + 1], tgt_h[r: r + 1]
            got.append(float(system_loss(params, *shard_batch(
                res.mesh, *(np.repeat(x, n_dev, axis=0) for x in row)
            ))))
            want.append(float(family["reference_loss"](
                params, *(jnp.asarray(x) for x in row)
            )))
        reference = {
            "system_loss": got,
            "reference_loss": want,
            **common.reference_error(got, want),
            "seconds": time.time() - t0,
        }

    sampler = ElasticDistributedSampler(
        dataset_size=len(data) - seq_len - 1,
        num_shards=jax_env.num_processes(),
        shard_rank=max(jax_env.process_id(), 0),
        seed=seed % (2 ** 31 - 1) + start_step,
    )
    batches = make_input_pipeline(
        traffic_gen.batch_stream(
            data, seq_len, trainer.local_samples_per_step, iter(sampler)
        ),
        h2d_fn=lambda b: trainer.shard_microbatches(*b),
        name="benchmark",
    )
    tokens_per_step = trainer.samples_per_step * seq_len
    save_every = int(traffic.get("save_every", 0)) if launched else 0
    disk_every = int(traffic.get("disk_every", 0))
    annotate = jax.profiler.TraceAnnotation

    steps_out = open(spec["steps_file"], "a") if launched else None
    records = []

    def emit(rec: dict) -> None:
        records.append(rec)
        if steps_out is not None:
            steps_out.write(json.dumps(rec) + "\n")
            steps_out.flush()

    def save(step: int, state, warm: bool = False) -> dict:
        """One flash checkpoint, every ``disk_every``-th of them to
        disk; returns what the record keeps. ``save_ok`` false is a
        save the program dropped (its agent still persisted the last
        one): reported, not retried."""
        to_disk = bool(
            disk_every and not warm
            and (step // save_every) % disk_every == 0
        )
        with annotate(SPAN_SAVE, step=step):
            t0 = time.time()
            ok = ckpt.save_checkpoint(
                step, state,
                storage_type=StorageType.DISK if to_disk else StorageType.MEMORY,
            )
            dt = time.time() - t0
            return {
                "save_step": step, "save_ok": bool(ok), "save_s": dt,
                "t_issued": t0, "to_disk": bool(to_disk),
                "checksum": int(checksum(state[0])),
                "step_programs": trainer._compiled._cache_size(),
                "compile_events": cache.total(),
            }

    report = {
        "device": device,
        "pid": os.getpid(),
        "start_step": start_step,
        "resumed": resumed,
        "restored_checksum": restored_checksum,
        "reference": reference,
        "tokens_per_step": tokens_per_step,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "t_proc_start": t_proc,
    }

    def write_report() -> None:
        if spec.get("report_file"):
            tmp = spec["report_file"] + ".tmp"
            with open(tmp, "w") as f:
                json.dump(report, f)
            os.replace(tmp, spec["report_file"])

    try:
        # -- warm-up: every shape the window uses, counted as set-up --
        step = start_step
        warm = int(traffic["warmup_steps"]) if not resumed else 1
        loss = None
        for _ in range(warm):
            step += 1
            tok, tgt = next(batches)
            if step == start_step + 1:
                report["step_hbm"] = step_hbm_bytes(
                    trainer, params, opt_state, tok, tgt
                )
                hits0, misses0 = cache.hits, cache.misses
            params, opt_state, loss = trainer.train_step(
                params, opt_state, tok, tgt
            )
            if step == start_step + 1:
                first_loss = materialize(loss, reason="log")
                t_first = time.time()
                TrainingMonitor.mark_phase("first_step_done")
                report.update(
                    first_step_done=t_first,
                    first_loss=first_loss,
                    first_step_cache_hits=cache.hits - hits0,
                    first_step_cache_misses=cache.misses - misses0,
                )
        if save_every and not resumed:
            # The save path too: the shared-memory segment is made here.
            report["warmup_save"] = save(step, (params, opt_state), warm=True)
        jax.block_until_ready(loss)
        report["cache_hits_before_window"] = cache.hits
        report["cache_misses_before_window"] = cache.misses
        compiles_before = cache.total()
        report["compile_events_before_window"] = compiles_before
        report["setup_s"] = time.time() - spec.get("t_start", t_proc)
        write_report()

        # -- the window --
        seconds = float(spec["seconds"])
        n_resume = int(traffic.get("resume_steps", 0))
        trace_on = bool(spec.get("trace")) and not resumed
        trace_start = int(traffic["trace_start_step"])
        trace_steps = int(traffic["trace_steps"])
        tracing = False
        t_open = time.time()
        emit({"window_open": t_open, "step": step, "pid": os.getpid()})
        prev = None  # (step, device loss, data_wait_s, dispatch_s, save)
        log_s = 0.0
        n = 0
        while True:
            n += 1
            step += 1
            if trace_on and n == trace_start:
                jax.block_until_ready(loss)
                jax.profiler.start_trace(spec["trace_dir"])
                tracing = True
                report["trace_window"] = {"t0": time.time(), "first_step": step}
            t_a = time.time()
            with annotate(SPAN_DATA, step=step):
                tok, tgt = next(batches)
            t_b = time.time()
            with annotate(SPAN_STEP, step=step):
                params, opt_state, loss = trainer.train_step(
                    params, opt_state, tok, tgt
                )
            t_c = time.time()
            if prev is not None:
                with annotate(SPAN_LOSS, step=prev["step"]):
                    prev["loss"] = materialize(prev.pop("dev"), reason="log")
                prev["t_done"] = time.time()
                prev["read_loss_s"] = prev["t_done"] - t_c
                prev["log_s"] = log_s  # what writing the last record took
                emit(prev)
                log_s = time.time() - prev["t_done"]
            prev = {"step": step, "dev": loss, "data_wait_s": t_b - t_a,
                    "dispatch_s": t_c - t_b}
            if save_every and step % save_every == 0 and not resumed:
                prev["save"] = save(step, (params, opt_state))
            if tracing and n == trace_start + trace_steps - 1:
                jax.block_until_ready(loss)
                report["trace_window"]["t1"] = time.time()
                report["trace_window"]["steps"] = trace_steps
                jax.profiler.stop_trace()
                tracing = False
                write_report()
            if resumed:
                if n >= n_resume:
                    break
            elif not launched and time.time() - t_open >= seconds:
                break
            # A launched first incarnation trains until it is killed.
        prev["loss"] = materialize(prev.pop("dev"), reason="log")
        jax.block_until_ready((params, loss))
        prev["t_done"] = time.time()
        emit(prev)
        emit({"window_close": prev["t_done"], "step": step})
        report.update(
            compiles_in_window=cache.total() - compiles_before,
            cache_misses_total=cache.misses,
            cache_hits_total=cache.hits,
            step_programs=trainer._compiled._cache_size(),
            last_step=step,
        )
        report["peak_bytes_in_use"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        )
    finally:
        batches.close()
        if steps_out is not None:
            steps_out.close()
        if ckpt is not None:
            ckpt.close()
    report["records"] = records if not launched else None
    write_report()
    return report


def main(argv=None) -> int:
    """Script mode: what the launcher runs. The spec is a file the
    parent wrote; a second incarnation reads the same one."""
    p = argparse.ArgumentParser()
    p.add_argument("--spec", required=True)
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    attempt = 0
    while os.path.exists(f"{spec['report_prefix']}{attempt}.json"):
        attempt += 1
    spec["report_file"] = f"{spec['report_prefix']}{attempt}.json"
    with open(spec["report_file"], "w") as f:
        json.dump({"pid": os.getpid(), "starting": True}, f)
    try:
        train(spec)
    except NotTheCell as exc:
        print(f"[bench] {exc}", file=sys.stderr, flush=True)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
