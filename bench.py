"""Benchmark: GPT-2 (124M, nanoGPT parity) training throughput per chip.

Prints ONE JSON line:
  {"metric": "nanogpt_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": R}

``vs_baseline`` is our model FLOPs utilisation (MFU) divided by the
reference's headline HFU claim of 49.6% on its thousand-GPU cluster
(BASELINE.md, docs/blogs/stabilize_llm_training_cn.md:351-353) — i.e.
>1.0 means this framework drives its chip harder than the reference
drove its GPUs on the same normalized scale.

Capture robustness: a chip belongs to one process at a time, and a
backend that cannot be reached may hang rather than raise. The parent
process therefore never imports jax. It health-probes the backend in
a subprocess under a hard timeout, retries with backoff until a
deadline, runs the measurement itself in a child process under its
own timeout, and prints exactly one parseable JSON line. Without a
measurement that line carries value 0.0 plus an ``error`` class, and
the exit code is 1. The measurement child refuses any backend but a
TPU unless ``BENCH_SMOKE=1``: a CPU timing is not this benchmark.

Env knobs:
  BENCH_MAX_WAIT_S     total retry budget, default 1200 (20 min)
  BENCH_PROBE_TIMEOUT  per-probe timeout, default 120 s
  BENCH_RUN_TIMEOUT    measurement-child timeout, default 900 s
  BENCH_REMAT / BENCH_BATCH_PER_CHIP / BENCH_STEPS
                       forwarded to the measurement child
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

REFERENCE_HFU = 0.496

_PROBE_SRC = """
import time
import jax
import jax.numpy as jnp
t0 = time.time()
x = jnp.ones((1024, 1024), jnp.bfloat16)
(x @ x).block_until_ready()
print("PROBE_OK", len(jax.devices()), round(time.time() - t0, 1))
"""


def measure() -> int:
    """The actual measurement. Runs in a child process: anything here may
    hang on an unreachable backend, and the parent's timeout absorbs
    that."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import gpt
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.step import (
        make_sharded_init,
        make_train_step,
        shard_batch,
    )

    # Progress beacon: the parent points DLROVER_TPU_BEACON_FILE at a
    # run-scoped path; we stamp step/phase boundaries so a backend
    # that hangs rather than raises leaves a readable
    # last-known-position for the parent's kind-"hang" ledger record.
    from dlrover_tpu.obs.beacon import default_beacon

    beacon = default_beacon()

    smoke = os.getenv("BENCH_SMOKE", "0") == "1"
    if jax.default_backend() != "tpu" and not smoke:
        print(
            "bench: a timing taken here is not this benchmark's "
            "metric (BENCH_SMOKE=1 rehearses the harness at a tiny "
            f"size): backend {jax.default_backend()!r} is not a TPU",
            file=sys.stderr,
        )
        return 1
    n_chips = len(jax.devices())
    mesh = build_mesh(MeshConfig(data=n_chips))

    # Tune-cache trial key: the *shipped* model dims + chip count +
    # backend + toolchain — everything that, when changed, makes a
    # cached winner meaningless. The pins themselves are the trial's
    # CONFIG, never part of the key (a key must index all pin
    # variants of the same measurement problem).
    from dlrover_tpu.common.runmeta import (
        package_version,
        trial_fingerprint,
    )

    _base = gpt.GPTConfig.gpt2()
    model_dims = {
        "n_layer": 2 if smoke else _base.n_layer,
        "n_head": 2 if smoke else _base.n_head,
        "n_embd": 128 if smoke else _base.n_embd,
        "block_size": 128 if smoke else _base.block_size,
        "vocab_size": 1024 if smoke else _base.vocab_size,
    }
    tune_key = trial_fingerprint(
        {
            "kind": "nanogpt_bench",
            "model": model_dims,
            "n_chips": n_chips,
            "dtype": str(_base.dtype),
            # Measurement mode, not a pin: a fresh-batch prefetch run
            # and a static-batch run are different problems.
            "prefetch": os.getenv("BENCH_PREFETCH", "0"),
            "backend": jax.default_backend(),
            "jax": package_version("jax"),
            "jaxlib": package_version("jaxlib"),
        }
    )
    # 124M-param GPT-2, block 1024. Measured on v5e (docs/ROOFLINE.md,
    # r4 sweep): full remat + flash 1024x1024 blocks (the kernel
    # defaults) + fused xent WITHOUT saved logits + batch 18 + XLA
    # norms is the best of {remat x batch x blocks x save-logits x
    # fused-norm}; the pure bf16 matmul ceiling on this chip measures
    # 153 TF/s = 0.78 of nominal peak, which bounds any MFU quoted
    # against nominal.
    # Autotune-persisted defaults, best-cached-trial first: the
    # persistent tune cache (accelerate/tune_cache.py — every bench
    # run records its pins+throughput there) supersedes the
    # write-once bench_tuned.json flow; "pinned" now simply means
    # "the best cached trial for this key". bench_tuned.json stays as
    # the legacy fallback (capture_perf still writes it for
    # noise-gated winners). Explicit BENCH_* env always wins; pins
    # only fill unset knobs, so the driver's plain `python bench.py`
    # runs the best measured config. BENCH_IGNORE_TUNED=1 gives a
    # true shipped-defaults run (the capture tool's baseline stage
    # sets it so tuned-vs-baseline can never compare tuned against
    # itself) — it skips the cache too. A corrupt file/cache must
    # degrade to defaults, not kill the bench.
    pins_source = None
    if os.getenv("BENCH_IGNORE_TUNED", "0") != "1":
        try:
            from dlrover_tpu.accelerate import tune_cache as _tc

            _cache = _tc.resolve()
            _best = _cache.best(tune_key) if _cache else None
        except Exception as _exc:  # noqa: BLE001
            print(f"# tune cache unavailable: {_exc!r}",
                  file=sys.stderr)
            _best = None
        if _best and isinstance(_best.get("config"), dict):
            # The cache is authoritative once it holds a best trial —
            # even one that applies no new pins (shipped defaults won,
            # or the env already sets every knob): falling through to
            # the legacy file would override the cache's measured
            # conclusion with stale pins.
            pins_source = "tune_cache"
            _applied = False
            for _k, _v in (_best["config"].get("pins") or {}).items():
                if _k not in os.environ:
                    os.environ[_k] = str(_v)
                    _applied = True
            print(
                "# tune-cache best trial "
                f"({_best.get('throughput')} @ {_best.get('ts')}): "
                + (
                    "pins applied"
                    if _applied
                    else "no new pins (env/shipped defaults already "
                    "match)"
                ),
                file=sys.stderr,
            )
        if pins_source is None:
            try:
                with open(
                    os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "bench_tuned.json",
                    )
                ) as _f:
                    # Provenance is "applied", not "agreed": a pin the
                    # env already carries stays attributed to the env.
                    for _k, _v in json.load(_f).get("pins", {}).items():
                        if _k not in os.environ:
                            os.environ[_k] = str(_v)
                            pins_source = "bench_tuned.json"
                if pins_source:
                    print("# applying bench_tuned.json autotune pins",
                          file=sys.stderr)
            except FileNotFoundError:
                pass
            except (ValueError, OSError, AttributeError) as _exc:
                print(f"# ignoring unreadable bench_tuned.json: {_exc}",
                      file=sys.stderr)

    # BENCH_REMAT: a remat.py policy name ("none"/"full"/"attention"/
    # "dots"/"offload"), or legacy 0/1 (= none/full).
    remat_env = os.getenv("BENCH_REMAT", "1")
    remat = ({"1": True, "0": False}.get(remat_env, remat_env))
    cfg = dataclasses.replace(
        gpt.GPTConfig.gpt2(),
        remat=remat,
        scan_unroll=int(os.getenv("BENCH_UNROLL", "1")),
    )
    # Autotune pins (tools/autotune_bwd_blocks.py winner -> the watch
    # loop re-runs with these): BENCH_BLOCKS="bq,bk,bqb,bkb",
    # BENCH_UNROLL=K.
    if os.getenv("BENCH_BLOCKS"):
        blocks = tuple(
            int(x) for x in os.environ["BENCH_BLOCKS"].split(",")
        )
        cfg = dataclasses.replace(cfg, attn_blocks=blocks)
    if os.getenv("BENCH_SMOKE", "0") == "1":
        # Tiny model: validates the capture path end-to-end (probe,
        # child, JSON relay) in seconds on any backend. Not a perf run.
        cfg = dataclasses.replace(
            cfg, n_layer=2, n_head=2, n_embd=128, block_size=128,
            vocab_size=1024,
        )
    xent_chunks = int(os.getenv("BENCH_XENT_CHUNKS", "8"))

    batch_per_chip = int(os.getenv("BENCH_BATCH_PER_CHIP", "18"))
    batch = batch_per_chip * n_chips
    steps = int(os.getenv("BENCH_STEPS", "20"))
    warmup = 3

    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    loss = functools.partial(
        gpt.loss_fn_fused, cfg=cfg, num_chunks=xent_chunks,
    )
    init, _ = make_sharded_init(
        mesh,
        functools.partial(gpt.init_params, cfg=cfg),
        gpt.param_logical_axes(cfg),
        optimizer,
    )
    params, opt_state = init(jax.random.PRNGKey(0))
    step = make_train_step(mesh, loss, optimizer)

    # The autotune pins in effect for THIS run (names+values — what
    # the emitted record and the bench ledger carry, so a
    # `bench_ledger compare` config mismatch is debuggable without
    # re-running), plus where the non-env ones came from.
    _PIN_KNOBS = (
        "BENCH_REMAT", "BENCH_BLOCKS", "BENCH_UNROLL",
        "BENCH_XENT_CHUNKS", "BENCH_BATCH_PER_CHIP",
    )
    effective_pins = {
        k: os.environ[k] for k in _PIN_KNOBS if k in os.environ
    }

    # BENCH_PREFETCH=1: fresh host batches every step, generated +
    # staged by the background prefetch pipeline (double-buffered
    # device_put overlapping compute) — measures the full
    # read-to-update path instead of re-feeding one static device
    # batch. Default 0 keeps the historical static-batch metric.
    prefetch_input = os.getenv("BENCH_PREFETCH", "0") == "1"
    pf = None
    if prefetch_input:
        import numpy as np

        from dlrover_tpu.data.prefetch import Prefetcher

        host_rng = np.random.default_rng(1)

        def batch_stream():
            while True:
                t = host_rng.integers(
                    0, cfg.vocab_size,
                    size=(batch, cfg.block_size), dtype=np.int32,
                )
                yield t, np.roll(t, -1, axis=1)

        pf = Prefetcher(
            batch_stream(),
            h2d_fn=lambda b: shard_batch(mesh, b[0], b[1]),
            name="bench",
        )
    else:
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(
            key, (batch, cfg.block_size), 0, cfg.vocab_size
        )
        targets = jnp.roll(tokens, -1, axis=1)
        tokens, targets = shard_batch(mesh, tokens, targets)

    # Fetch-then-dispatch: every fetched batch is trained on, and the
    # loop never pays a trailing fetch for a batch it will discard.
    if beacon is not None:
        beacon.stamp(phase="compile")
    for _ in range(warmup):
        if pf is not None:
            tokens, targets = next(pf)
        params, opt_state, metrics = step(
            params, opt_state, tokens, targets
        )
    # float() reads the loss back: the warm-up's one host sync.
    float(metrics["loss"])

    if pf is not None:
        pf.wait_s_total = 0.0  # count data-wait for measured steps only
    start = time.time()
    for i in range(steps):
        if beacon is not None:
            beacon.stamp(step=i + 1, phase="dispatch")
        if pf is not None:
            tokens, targets = next(pf)
        params, opt_state, metrics = step(
            params, opt_state, tokens, targets
        )
    float(metrics["loss"])
    if beacon is not None:
        beacon.stamp(step=steps, phase="device_execute")
    elapsed = time.time() - start
    data_wait_s = pf.wait_s_total if pf is not None else 0.0
    if pf is not None:
        pf.close()

    tokens_per_step = batch * cfg.block_size
    tokens_per_sec = tokens_per_step * steps / elapsed
    per_chip = tokens_per_sec / n_chips

    # The attached TPU's peak from the one table (a TPU that is not
    # in it is an error); the CPU smoke run is priced against the v5e
    # it rehearses for.
    from dlrover_tpu.utils.profiler import chip_peaks

    peak_tflops = chip_peaks(default="v5e")[0]
    flops_per_token = gpt.flops_per_token(cfg)
    mfu = (tokens_per_sec * flops_per_token) / (
        peak_tflops * 1e12 * n_chips
    )
    vs_baseline = mfu / REFERENCE_HFU

    print(
        json.dumps(
            {
                "metric": "nanogpt_tokens_per_sec_per_chip",
                "value": round(per_chip, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(vs_baseline, 4),
                # Raw MFU vs nominal peak, so the tokens/s value and the
                # HFU-normalized ratio can never be conflated downstream.
                "mfu": round(mfu, 4),
                # Only the child knows the real backend (the parent
                # never imports jax); the parent's provenance stamp
                # and the ledger record key on it.
                "backend": jax.default_backend(),
                # Applied autotune pins (names+values) + provenance
                # and the tune-cache key — the ledger carries all of
                # it, and capture_perf reuses the key to consult the
                # cache before re-sweeping.
                "pins": effective_pins,
                **(
                    {"pins_source": pins_source} if pins_source else {}
                ),
                "tune_key": tune_key,
                **(
                    {"data_wait_s": round(data_wait_s, 4)}
                    if prefetch_input
                    else {}
                ),
            }
        )
    )
    # Every successful measurement becomes a cached trial: "the pin
    # file" is now just the best trial for this key, and the next run
    # (or capture window) starts from it instead of re-earning it.
    try:
        from dlrover_tpu.accelerate import tune_cache as _tc

        _cache = _tc.resolve()
        if _cache is not None:
            _cache.record(
                tune_key,
                {"pins": effective_pins},
                per_chip,
                extra={
                    "mfu": round(mfu, 4),
                    "vs_baseline": round(vs_baseline, 4),
                    "stage": os.getenv("BENCH_LEDGER_STAGE", "adhoc"),
                },
            )
    except Exception as _exc:  # noqa: BLE001 — bookkeeping never
        # outranks the measurement
        print(f"# tune cache record failed: {_exc!r}", file=sys.stderr)
    print(
        f"# chips={n_chips} batch={batch} steps={steps} "
        f"elapsed={elapsed:.2f}s mfu={mfu:.3f} "
        f"loss={float(metrics['loss']):.3f}"
        + (f" data_wait={data_wait_s:.3f}s" if prefetch_input else ""),
        file=sys.stderr,
    )
    return 0


def _run_child(argv: list[str], timeout_s: float) -> tuple[str, str, str]:
    """Run argv; return (stdout, status, detail). status is "ok",
    "timeout", or "error".

    The child runs in its own session so a timeout kills the whole
    process group — a grandchild holding the pipes open must not be
    able to block the parent past the deadline."""
    proc = subprocess.Popen(
        argv,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        start_new_session=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired as exc:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            out, err = proc.communicate(timeout=15)
        except (subprocess.TimeoutExpired, ValueError):
            out, err = exc.output or "", exc.stderr or ""
        detail = f"no response within {timeout_s:.0f}s"
        partial = (err or out or "").strip().splitlines()
        if partial:
            detail += f"; last output: {partial[-1][:200]}"
        return "", "timeout", detail
    if err:
        sys.stderr.write(err[-4000:])
    if proc.returncode != 0:
        tail = (err or out or "").strip().splitlines()
        return "", "error", tail[-1][:300] if tail else f"rc={proc.returncode}"
    return out, "ok", ""


# Transient signatures are checked FIRST: jax surfaces a backend that
# is not up yet as e.g. "XlaRuntimeError: UNAVAILABLE: ...", which
# must stay retryable even though it contains an *Error name. Then
# deterministic Python crash signatures forfeit the budget
# immediately. Anything unrecognized defaults to RETRYABLE — the
# failure texts vary (DEADLINE_EXCEEDED, connection reset, truncated
# stderr, ...), and a wasted retry budget is cheaper than misretrying
# never.
_TRANSIENT_SIGNATURES = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "initialize backend",
    "onnection",  # Connection/connection reset/refused
    "timed out",
)
_DETERMINISTIC_SIGNATURES = (
    "ImportError",
    "ModuleNotFoundError",
    "SyntaxError",
    "AttributeError",
    "NameError",
    "TypeError",
    "ValueError",
    "KeyError",
    "IndexError",
    "AssertionError",
    "child printed no JSON",
    "is not a TPU",
)


def _classify(status: str, detail: str) -> str:
    if status == "never_ran":
        return "budget_exhausted"
    if status == "timeout":
        return "tpu_hang"
    if any(sig in detail for sig in _TRANSIENT_SIGNATURES):
        return "tpu_unavailable"
    if any(sig in detail for sig in _DETERMINISTIC_SIGNATURES):
        return "bench_error"
    return "tpu_unavailable"


def _ledger_append(rec: dict) -> None:
    """Append ``rec`` to BENCH_LEDGER.jsonl (BENCH_NO_LEDGER=1
    skips). Never raises: a broken ledger must not fail (or fail to
    report) a hard-won measurement."""
    if os.getenv("BENCH_NO_LEDGER", "0") == "1":
        return
    try:
        sys.path.insert(
            0,
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"
            ),
        )
        import bench_ledger

        bench_ledger.append_record(rec)
    except Exception as exc:  # noqa: BLE001
        print(f"# ledger append failed: {exc!r}", file=sys.stderr)


def _stamp_and_ledger(line: str) -> str:
    """Provenance-stamp the child's JSON record (host/backend/jax
    versions — the shared runmeta helper, so this artifact can never
    be backend-ambiguous) and append it to the bench ledger. Any
    failure returns the original line: the bench's one-JSON-line
    contract outranks the bookkeeping."""
    try:
        rec = json.loads(line)
        from dlrover_tpu.common.runmeta import run_metadata

        rec["meta"] = run_metadata(backend=rec.get("backend"))
        _ledger_append(rec)
        return json.dumps(rec)
    except Exception as exc:  # noqa: BLE001
        print(f"# provenance stamp failed: {exc!r}", file=sys.stderr)
        return line


def _read_final_beacon() -> dict:
    """The measurement child's last progress stamp (step / phase /
    staleness), read from the beacon file AFTER the child is dead —
    the whole point of the mmap'd beacon is that it outlives a wedged
    writer. Empty dict when the child never stamped."""
    try:
        from dlrover_tpu.obs import beacon as _beacon

        stamp = _beacon.read_beacon()
        if not stamp:
            return {}
        out = {
            k: stamp.get(k)
            for k in ("pid", "step", "microbatch", "phase", "seq")
        }
        age = _beacon.stamp_age(stamp)
        if age is not None:
            out["age_s"] = round(age, 1)
        return out
    except Exception:  # noqa: BLE001 — forensics never outrank the
        # failure record
        return {}


def _emit_failure(error_class: str, detail: str, attempts: int) -> None:
    rec = {
        "metric": "nanogpt_tokens_per_sec_per_chip",
        "value": 0.0,
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,
        "error": error_class,
        "detail": detail[:300],
        "attempts": attempts,
    }
    if error_class == "tpu_hang":
        # A timeout is a hang, and the beacon says WHERE: the record
        # kind + last stamp turn "rc=124" into "wedged at step K's
        # dispatch" (ROADMAP item 1's blind-retry seam).
        rec["kind"] = "hang"
        stamp = _read_final_beacon()
        if stamp:
            rec["beacon"] = stamp
            rec["hang_digest"] = (
                f"child last stamped step {stamp.get('step')} "
                f"{stamp.get('phase')}"
                + (
                    f" microbatch {stamp.get('microbatch')}"
                    if (stamp.get("microbatch") or -1) >= 0
                    else ""
                )
                + (
                    f", {stamp['age_s']:.0f}s before the kill"
                    if isinstance(stamp.get("age_s"), (int, float))
                    else ""
                )
            )
            print(f"# {rec['hang_digest']}", file=sys.stderr)
    try:
        from dlrover_tpu.common.runmeta import run_metadata

        rec["meta"] = run_metadata()
    except Exception:  # noqa: BLE001 — the failure record must
        # print even from a broken tree
        pass
    # Cross-reference, NOT a substitute: if this round already landed
    # a live-chip measurement (tools/capture_perf.py appends every
    # success to PERF_r05.json with a timestamp), point at it so a
    # dead capture window is distinguishable from "never
    # measured". The reported value stays 0.0 — only a live run
    # counts.
    try:
        hist = json.load(open(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "PERF_r05.json")))
        if isinstance(hist, list) and hist:
            last = hist[-1]
            rec["last_measured_this_round"] = {
                k: last.get(k)
                for k in ("value", "vs_baseline", "stage", "ts")
            }
    except Exception:  # noqa: BLE001 — no record, nothing to point at
        pass
    # Failed captures are ledgered too (never as comparison
    # endpoints): a dead capture window must be visible in the
    # history, not silently absent.
    _ledger_append(rec)
    print(json.dumps(rec))


def main() -> int:
    # Run-scoped beacon file, inherited by the measurement child: the
    # child stamps progress into it, and on a timeout the parent reads
    # the dead child's last position for the kind-"hang" record.
    os.environ.setdefault(
        "DLROVER_TPU_BEACON_FILE",
        os.path.join(
            os.getenv("TMPDIR", "/tmp"),
            f"dlrover_tpu_beacon_bench_{os.getpid()}.json",
        ),
    )
    max_wait = float(os.getenv("BENCH_MAX_WAIT_S", "1200"))
    probe_timeout = float(os.getenv("BENCH_PROBE_TIMEOUT", "120"))
    run_timeout = float(os.getenv("BENCH_RUN_TIMEOUT", "900"))
    deadline = time.time() + max_wait

    backoff = 30.0
    attempts = 0
    last_status, last_detail = "never_ran", "no attempt completed"
    while True:
        # Clamp every child to the remaining budget so total wall time
        # stays within BENCH_MAX_WAIT_S even when a child hangs.
        remaining = deadline - time.time()
        if remaining < 30:
            break
        attempts += 1
        probe_out, status, detail = _run_child(
            [sys.executable, "-c", _PROBE_SRC],
            min(probe_timeout, remaining),
        )
        if status == "ok":
            print(
                f"# probe ok (attempt {attempts}): {probe_out.strip()}",
                file=sys.stderr,
            )
            remaining = deadline - time.time()
            if remaining < 60:
                last_status = "timeout"
                last_detail = "probe ok but <60s budget left for the run"
                break
            out, status, detail = _run_child(
                [sys.executable, os.path.abspath(__file__), "--child"],
                min(run_timeout, remaining),
            )
            if status == "ok":
                # Relay the child's JSON result line, stamped with
                # the run's provenance and appended to the bench
                # ledger (the regression-gated history a lost capture
                # window can never erase).
                for line in out.splitlines():
                    if line.startswith("{"):
                        print(_stamp_and_ledger(line))
                        return 0
                status, detail = "error", "child printed no JSON line"
        last_status, last_detail = status, detail
        print(
            f"# attempt {attempts} failed ({status}): {detail}",
            file=sys.stderr,
        )
        if _classify(status, detail) == "bench_error":
            # Deterministic failure (import error, bad JSON, crash in
            # measure()): retrying cannot help, report immediately.
            break
        remaining = deadline - time.time()
        if remaining <= backoff:
            break
        time.sleep(min(backoff, remaining))
        backoff = min(backoff * 2, 120.0)

    _emit_failure(_classify(last_status, last_detail), last_detail, attempts)
    return 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(measure())
    sys.exit(main())
