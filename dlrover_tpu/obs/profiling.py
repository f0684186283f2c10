"""Performance observability: step phases, compile accounting, MFU.

The control plane can observe everything about a job *except* where
its time goes; this module closes that gap for the training hot path:

* :class:`StepPhaseProfiler` attributes every step's wall time into
  five exhaustive phases — ``data_wait`` (blocking on the input
  pipeline's host side), ``h2d_stage`` (the host->device staging
  slice of the input wait), ``compile`` (dispatches that traced +
  XLA-compiled), ``dispatch`` (host-side enqueue of an
  already-compiled step), and ``device_execute`` (the residual: the
  device working while the host runs ahead) — into
  ``dlrover_step_phase_seconds_total{phase}``.
  The clock is injectable, so attribution is testable hermetically.
* :class:`CompileTracker` counts (re)compilations per jitted function
  via its dispatch-cache size (``dlrover_compile_total{fn}`` /
  ``dlrover_compile_seconds_total{fn}``): a shape drift that silently
  retraces every step shows up as a counter slope, not a mystery.
* :func:`compiled_scopes` describes a tracked function's compiled
  program instruction by instruction, by the ``jax.named_scope`` each
  came from and the pass (forward, backward, recompute) it belongs to:
  what turns a device profile's ``fusion.364`` into ``layers/mlp``.
  Nothing is lowered or compiled for it until it is called.
* :func:`install_compile_listeners` / :func:`startup_timeline`: JAX's
  own account of every trace, lowering, backend compile and
  persistent-cache load (``jax.monitoring``), kept by function as
  ``{stage, fn, t0, t1}`` records beside the phase marks this process
  placed: what a start is made of, asked for in one call
  (``dlrover_compile_stage_seconds_total{stage}``; spans ``jax.*``
  when the tracer is on).
* :class:`MfuMeter` turns XLA's own cost model
  (``jit(f).lower(*args).cost_analysis()`` — trace+lower only, never
  a second XLA compile) plus measured step time into a live
  ``dlrover_train_mfu`` gauge (and ``dlrover_train_flops_per_step``).
* The **PROFILE action** file protocol: the master pushes a
  ``profile`` heartbeat action (straggler auto-trigger or operator
  RPC), the agent drops a request file, the trainer's profiler picks
  it up between steps, captures an N-step phase breakdown (plus an
  optional ``jax.profiler`` trace), and writes a digest file the
  agent ships back over the existing ``DiagnosticsReport`` channel.

Everything here is stdlib-only except the two lazily-imported jax
touchpoints (FLOPs derivation, optional profiler trace), so the phase
accounting and the capture protocol stay hermetically testable.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import re
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from dlrover_tpu.common.config import tmp_path
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.obs.beacon import ProgressBeacon, default_beacon
from dlrover_tpu.obs.metrics import counter, gauge
from dlrover_tpu.obs.tracer import completed_span
from dlrover_tpu.obs.tracer import event as obs_event
from dlrover_tpu.obs.tracer import span as obs_span

logger = get_logger("profiling")

# The exhaustive per-step wall-time phases, in attribution precedence.
# ``data_wait`` is host-side input wait (pulling/collating the next
# batch); ``h2d_stage`` is the host->device staging slice of that wait
# (the split makes a device-prefetch win attributable: a healthy
# device-resident pipeline drives BOTH toward zero, while a hidden H2D
# stall shows up as h2d_stage specifically).
PHASES = ("data_wait", "h2d_stage", "compile", "dispatch", "device_execute")

PROFILE_REQUEST_ENV = "DLROVER_TPU_PROFILE_REQUEST_FILE"
PROFILE_DIGEST_ENV = "DLROVER_TPU_PROFILE_DIGEST_FILE"
PROFILE_STEPS_ENV = "DLROVER_TPU_PROFILE_STEPS"
PROFILE_TRACE_DIR_ENV = "DLROVER_TPU_PROFILE_TRACE_DIR"
PEAK_TFLOPS_ENV = "DLROVER_TPU_PEAK_TFLOPS"
MFU_ENV = "DLROVER_TPU_MFU"

DEFAULT_PROFILE_STEPS = 20

_PHASE_SECONDS = counter(
    "dlrover_step_phase_seconds_total",
    "Training wall time attributed by step phase (data_wait / "
    "h2d_stage / compile / dispatch / device_execute); the five "
    "phases partition each step's wall time exactly — data_wait is "
    "host-side input wait, h2d_stage the host->device staging slice "
    "of it",
    ("phase",),
)
_COMPILE_TOTAL = counter(
    "dlrover_compile_total",
    "XLA (re)compilations observed per jitted function",
    ("fn",),
)
_COMPILE_SECONDS = counter(
    "dlrover_compile_seconds_total",
    "Wall seconds spent in dispatches that traced + compiled, per "
    "jitted function",
    ("fn",),
)
_STAGE_SECONDS = counter(
    "dlrover_compile_stage_seconds_total",
    "Wall seconds JAX reported per stage of its compile pipeline "
    "(trace / lower / backend_compile / cache_load, by "
    "jax.monitoring) plus the trainer's pricing of its step (price)",
    ("stage",),
)
_MFU = gauge(
    "dlrover_train_mfu",
    "Live model FLOPs utilisation: cost-analysis FLOPs per step over "
    "measured step time, vs the chip's peak (windowed mean)",
)
_FLOPS_PER_STEP = gauge(
    "dlrover_train_flops_per_step",
    "FLOPs one optimizer step costs per XLA cost analysis",
)
_PROFILE_CAPTURES = counter(
    "dlrover_profile_captures_total",
    "On-demand PROFILE captures completed by this trainer",
)


def _job_scoped(name: str) -> str:
    job = os.getenv("DLROVER_TPU_JOB_NAME", "default")
    return tmp_path(f"dlrover_tpu_{name}_{job}.json")


def profile_request_file() -> str:
    """Agent -> trainer: where a PROFILE request is dropped. Job-
    scoped (two jobs on one host must not trigger each other)."""
    return os.getenv(PROFILE_REQUEST_ENV, _job_scoped("profile_request"))


def profile_digest_file() -> str:
    """Trainer -> agent: where the capture digest lands."""
    return os.getenv(PROFILE_DIGEST_ENV, _job_scoped("profile_digest"))


_request_counter = [0]
_request_lock = threading.Lock()


def write_profile_request(
    steps: int = 0, trace_dir: str = "", path: Optional[str] = None
) -> str:
    """Drop a PROFILE request for the co-hosted trainer; returns the
    request id the digest will echo. Atomic (tmp+rename) so the
    trainer never reads a torn request."""
    with _request_lock:
        _request_counter[0] += 1
        seq = _request_counter[0]
    req_id = f"{os.getpid()}-{int(time.time() * 1000)}-{seq}"
    req = {
        "id": req_id,
        "steps": int(
            steps
            or os.getenv(PROFILE_STEPS_ENV, str(DEFAULT_PROFILE_STEPS))
        ),
        "trace_dir": trace_dir or os.getenv(PROFILE_TRACE_DIR_ENV, ""),
        "ts": time.time(),
    }
    path = path or profile_request_file()
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(req, f)
    os.replace(tmp, path)
    return req_id


def read_profile_digest(
    expect_id: Optional[str] = None, path: Optional[str] = None
) -> Optional[dict]:
    """The digest the trainer wrote, or None when absent / not yet the
    one answering ``expect_id``."""
    path = path or profile_digest_file()
    try:
        with open(path) as f:
            digest = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(digest, dict):
        return None
    if expect_id is not None and digest.get("id") != expect_id:
        return None
    return digest


def peak_flops_per_s() -> Optional[float]:
    """The chip's peak FLOP/s for the MFU denominator; None off a
    TPU, where a utilisation means nothing and the gauge stays unset.

    ``DLROVER_TPU_PEAK_TFLOPS`` overrides (tests); otherwise the
    generation table in utils/profiler resolves the live device kind,
    and a TPU that is not in it is an error."""
    env = os.getenv(PEAK_TFLOPS_ENV, "")
    if env:
        try:
            return float(env) * 1e12
        except ValueError:
            logger.warning("unparseable %s=%r", PEAK_TFLOPS_ENV, env)
    from dlrover_tpu.utils.profiler import _device_peak_tflops

    peak = _device_peak_tflops()
    return None if peak is None else peak * 1e12


def step_flops(jfn, *args) -> Optional[float]:
    """FLOPs per call of a jitted function, priced by XLA's own cost
    model on the *lowered* module — trace + lower only, which is
    cheap next to an XLA compile and never triggers a second one.
    Must be called BEFORE the first dispatch when arguments will be
    donated (lowering only reads shapes; dispatch deletes buffers).
    Returns None when the backend can't price the module."""
    try:
        cost = jfn.lower(*args).cost_analysis()
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:  # noqa: BLE001 — backend-dependent analysis
        logger.debug("lowered cost_analysis unavailable", exc_info=True)
        return None


# -- the start-up timeline: marks, and JAX's compile pipeline by function

# Phase marks of this prefix are the agent's (TrainingMonitor.mark_phase).
AGENT_MARK_PREFIX = "agent."
# What a writer's new set of marks displaces is kept one generation
# under this prefix.
PREV_MARK_PREFIX = "prev."
# The marks that start a writer's new set: the trainer's, the agent's.
NEW_SET_MARKS = ("proc_start", AGENT_MARK_PREFIX + "exit_seen")

# jax.monitoring's time-span events (start and end on time.time(),
# ``fun_name`` the traced function's or the module's name) -> stage.
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# JAX's four (``price``, the trainer's pricing of its step, is a
# record's stage too and no stage of a compile).
STAGES = ("trace", "lower", "backend_compile", "cache_load")
# A start places a hundred or two records (each jitted function's
# outermost trace, its lowering, its compile or load); the newest are
# kept.
MAX_STAGE_RECORDS = 4096


def _is_agents_mark(key: str) -> bool:
    """Whose mark a key is: a ``prev.`` key belongs to the writer of
    the name behind the prefix."""
    return key.removeprefix(PREV_MARK_PREFIX).startswith(AGENT_MARK_PREFIX)


def place_mark(marks: dict, name: str, now: float) -> None:
    """``marks[name] = now``; a mark that starts a writer's new set
    first moves that writer's marks one generation back, under
    ``prev.``, and drops the generation before."""
    if name in NEW_SET_MARKS:
        agents = _is_agents_mark(name)
        mine = [k for k in marks if _is_agents_mark(k) == agents]
        last = {
            PREV_MARK_PREFIX + k: marks[k]
            for k in mine if not k.startswith(PREV_MARK_PREFIX)
        }
        for k in mine:
            del marks[k]
        marks.update(last)
    marks[name] = now


class _StartupTimeline:
    """What this process keeps of its start, under one lock: every
    phase mark placed here (``name -> time.time()``) and the stage
    records of JAX's listeners in arrival order."""

    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.marks: Dict[str, float] = {}
        self.records: collections.deque = collections.deque(
            maxlen=MAX_STAGE_RECORDS
        )
        # Per thread. ``loaded_at``: when JAX reported a cache load
        # whose enclosing backend-compile span is still open;
        # ``open_stages``: the stages JAX has begun and not ended.
        self.pending = threading.local()

    def record(self, stage: str, fn: str, t0: float, t1: float) -> None:
        _STAGE_SECONDS.inc(max(t1 - t0, 0.0), stage=stage)
        with self.lock:
            self.records.append(
                {"stage": stage, "fn": fn, "t0": t0, "t1": t1}
            )

    # -- jax.monitoring's listeners ---------------------------------------

    def on_time_span(self, event, start_time, end_time, **kwargs) -> None:
        stage = _JAX_STAGES.get(event)
        if stage is None:
            return
        # A function traced while another stage is open on the thread
        # (inside another's trace: the one-operation functions of
        # jax.numpy, thousands in a step whose layers stand in line;
        # inside a lowering: the rules' own helpers) is part of that
        # stage: only the outermost trace is a record.
        depth = max(getattr(self.pending, "open_stages", 1) - 1, 0)
        self.pending.open_stages = depth
        if stage == "trace":
            if depth:
                return
        elif stage == "backend_compile":
            # JAX's span is around compile_or_get_cached: a request
            # the persistent cache served is a load, not a compile,
            # and this is where its function's name is known.
            loaded_at = getattr(self.pending, "loaded_at", None)
            self.pending.loaded_at = None
            if loaded_at is not None and loaded_at >= start_time:
                stage = "cache_load"
        fn = str(kwargs.get("fun_name", ""))
        self.record(stage, fn, start_time, end_time)
        completed_span(f"jax.{stage}", start_time, end_time, fn=fn)

    def on_scalar(self, event, value, **_) -> None:
        # JAX reports a stage's start as a scalar, its end as a span.
        if event in _JAX_STAGES:
            self.pending.open_stages = (
                getattr(self.pending, "open_stages", 0) + 1
            )

    def on_duration(self, event, duration, **_) -> None:
        if event == _JAX_CACHE_LOAD:
            self.pending.loaded_at = time.time()

    def since(self, t_from: float) -> Dict[str, List[dict]]:
        """The records that ended at or after ``t_from``, by stage."""
        out: Dict[str, List[dict]] = {}
        with self.lock:
            for rec in reversed(self.records):
                if rec["t1"] < t_from:
                    break
                out.setdefault(rec["stage"], []).append(rec)
        return out


_TIMELINE = _StartupTimeline()


def install_compile_listeners() -> bool:
    """Listen to JAX's own account of its compile pipeline: every
    trace, lowering and backend compile with the function's name and
    its start and end (the outermost trace only: a function traced
    inside another's trace or lowering is part of it), every load
    from the persistent cache.
    Idempotent; registers nothing (and returns False) in a process
    that has not imported ``jax``, which then has nothing to compile
    either. JAX calls a listener at a compile and never at a cached
    dispatch."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    with _TIMELINE.lock:
        if _TIMELINE.installed:
            return True
        _TIMELINE.installed = True
    jax.monitoring.register_event_time_span_listener(_TIMELINE.on_time_span)
    jax.monitoring.register_event_duration_secs_listener(
        _TIMELINE.on_duration
    )
    jax.monitoring.register_scalar_listener(_TIMELINE.on_scalar)
    return True


def union_seconds(records) -> float:
    """Seconds the records cover together, an overlap counted once
    (two threads compiling at a time)."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted((r["t0"], r["t1"]) for r in records):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def keep_mark(name: str, now: float) -> None:
    """A phase mark of this process (TrainingMonitor.mark_phase)."""
    with _TIMELINE.lock:
        place_mark(_TIMELINE.marks, name, now)


def startup_timeline() -> dict:
    """This process's start as the program saw it: ``{"marks": the
    phase marks placed here, ``prev.`` keys and all (the file, where
    there is one, also holds the other writer's), "compile": [{stage,
    fn, t0, t1}] in arrival order (``trace``, ``lower``,
    ``backend_compile``, ``cache_load`` with JAX's own times on
    ``time.time()``; ``price``)}``."""
    with _TIMELINE.lock:
        return {
            "marks": dict(_TIMELINE.marks),
            "compile": [dict(r) for r in _TIMELINE.records],
        }


# The newest tracker of each function name: what compiled_scopes asks
# once the loop that owned the trainer has returned. A tracker holds
# the jitted function and an abstract signature, never a device
# buffer; a new trainer's tracker replaces the old one.
_TRACKERS: Dict[str, "CompileTracker"] = {}


def _abstract(x):
    """Shape, dtype and sharding of one argument leaf, no buffer. An
    array nobody committed to a device lowers as one again."""
    import jax

    sharding = getattr(x, "sharding", None)
    if not getattr(x, "_committed", True):
        sharding = None
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding,
        weak_type=getattr(x, "weak_type", False),
    )


class CompileTracker:
    """Detects which dispatches of a jitted callable (re)compiled.

    Primary signal: growth of the jit dispatch cache
    (``jfn._cache_size()``), which catches silent retraces from shape
    or dtype drift mid-run. Fallback (no cache API): only the first
    observed call counts as the compile.

    Its ``trainer.compile`` event also says what JAX did in the call
    (:func:`install_compile_listeners`): ``trace_s``, ``lower_s``,
    ``backend_compile_s``, ``cache_load_s``, ``cache_hit``.

    Given the arguments of the calls it observes, it remembers the
    first call's abstract signature (shape, dtype, sharding of every
    leaf; donated arrays keep those once deleted), which is all
    :func:`compiled_scopes` needs to lower the same program again.
    """

    def __init__(self, fn_name: str, jfn=None):
        self.fn_name = fn_name
        self._jfn = jfn
        self._last_cache_size: Optional[int] = None
        self._calls = 0
        self.compiles = 0
        self.signature = None
        install_compile_listeners()
        if jfn is not None:
            _TRACKERS[fn_name] = self

    def _cache_size(self) -> Optional[int]:
        probe = getattr(self._jfn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:  # noqa: BLE001 — private API, best-effort
            return None

    def price(self, *args) -> Optional[float]:
        """FLOPs one call of the tracked function costs
        (:func:`step_flops`), under span ``trainer.price_step``
        (``flops``). What the pricing costs a start is also on the
        start-up timeline as stage ``price``, tracer or no tracer."""
        t0 = time.time()
        with obs_span("trainer.price_step") as span:
            flops = step_flops(self._jfn, *args)
            span.set(flops=flops)
        _TIMELINE.record("price", self.fn_name, t0, time.time())
        return flops

    def observe_call(self, dur_s: float, args=None) -> bool:
        """Record one dispatch of ``args`` lasting ``dur_s``; True
        when it (re)compiled."""
        self._calls += 1
        if self.signature is None and args is not None:
            import jax

            self.signature = jax.tree.map(_abstract, args)
        size = self._cache_size()
        if size is None:
            compiled = self._calls == 1
        else:
            compiled = (
                self._last_cache_size is None
                or size > self._last_cache_size
            )
            self._last_cache_size = size
        if compiled:
            self.compiles += 1
            _COMPILE_TOTAL.inc(fn=self.fn_name)
            _COMPILE_SECONDS.inc(max(dur_s, 0.0), fn=self.fn_name)
            # What JAX did in this call: a retrace, a cold compile
            # or a load from the persistent cache.
            stages = _TIMELINE.since(time.time() - max(dur_s, 0.0))
            obs_event(
                "trainer.compile",
                fn=self.fn_name,
                dur_s=round(dur_s, 4),
                total=self.compiles,
                cache_hit=bool(stages.get("cache_load")),
                **{
                    f"{stage}_s": round(
                        union_seconds(stages.get(stage, ())), 4
                    )
                    for stage in STAGES
                },
            )
            if self.compiles > 1:
                logger.warning(
                    "%s recompiled (compile #%d, %.2fs): check for "
                    "shape/dtype drift in the input pipeline",
                    self.fn_name, self.compiles, dur_s,
                )
        return compiled


# The program's jax.named_scope vocabulary (models/, trainer/), as
# compiled_scopes reports it. "layers" and "accumulate" are the two
# scans' own scopes: they own what no layer scope inside them does;
# "ut_loop" is a looped model's passes (models/ouro.py: the calls of
# its one jitted pass in a row; its own is the norm that closes a pass
# and the stacking of the passes' outputs), "exit_gate" its gate, exit
# distribution and entropy; "attn_window" and "attn_full" are the two
# kinds of attention layer of a patterned stack (models/mellum.py),
# "moe_balance" the balance loss a stack adds beside a held expert
# layer, "mla_rope" a latent mixer's rotation (models/deepseek_v2.py);
# "selscan" a per-channel selective scan and "gmu" a gated memory unit
# (both inside "ssm"), "attn_cross" a layer that attends another
# layer's keys and values and "attn_diff" what differential attention
# does outside its two flash calls (models/phi4_flash.py).
SCOPES = frozenset((
    "accumulate", "layers", "embed", "attn", "mlp", "ssm", "head",
    "optimizer", "moe_route", "moe_experts", "moe_combine", "ssm_conv",
    "ssd", "ssm_norm", "ut_loop", "exit_gate", "kda", "kda_conv",
    "kda_scan", "kda_gate", "mla", "moe_routed", "moe_shared",
    "attn_window", "attn_full", "moe_balance", "mla_rope",
    "selscan", "gmu", "attn_diff", "attn_cross",
))
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME_STACK_WRAPPER = re.compile(r"\b(?:jvp|transpose|vmap)\(|\)")


def scope_of(op_name: str) -> Dict[str, str]:
    """An HLO ``op_name`` (the name stack JAX wrote at tracing) ->
    ``{"scope", "pass"}``: the program's scopes on it, outermost
    first (``"accumulate/layers/mlp/moe_route"``; ``""`` with none),
    and ``"recompute"`` under ``rematted_computation`` (what remat
    computes again, part of the backward), else ``"bwd"`` under a
    ``transpose(``, else ``"fwd"``."""
    parts = _NAME_STACK_WRAPPER.sub("", op_name).split("/")
    if "rematted_computation" in parts:
        which = "recompute"
    elif "transpose(" in op_name:
        which = "bwd"
    else:
        which = "fwd"
    return {
        "scope": "/".join(p for p in parts if p in SCOPES),
        "pass": which,
    }


def compiled_scopes(fn_name: str) -> Optional[Dict[str, dict]]:
    """Every instruction of ``fn_name``'s compiled program, by name:
    ``{"scope", "pass", "op_name"}`` (:func:`scope_of`; all three
    empty or ``"fwd"`` where the compiler wrote no ``op_name``: its
    own copies, tuples, parameters). A device profile names an event
    by its instruction, so this is the join from ``fusion.364`` to
    ``layers/mlp``; a fusion carries its root's name.

    Lowers and compiles the newest tracked function of that name for
    the signature of the first call its :class:`CompileTracker`
    observed, and only when this is called. In the process that ran
    the step JAX serves both from what it kept (0.04-0.14 s on a v5e
    for the benchmark's steps); at worst it is a lowering and a
    compile the persistent cache serves. None when no such function
    has been called in this process."""
    tracker = _TRACKERS.get(fn_name)
    if tracker is None or tracker.signature is None:
        return None
    text = tracker._jfn.lower(*tracker.signature).compile().as_text()
    out = {}
    for line in text.splitlines():
        head = _HLO_INSTRUCTION.match(line)
        if head is None:
            continue
        named = _HLO_OP_NAME.search(line)
        op_name = named.group(1) if named else ""
        out[head.group(1)] = {**scope_of(op_name), "op_name": op_name}
    return out


class MfuMeter:
    """FLOPs/step + measured step seconds -> live MFU gauge.

    Step times feed a bounded window; the gauge is the windowed-mean
    utilisation, which absorbs the host-side pacing jitter of the
    zero-sync loop (individual samples are dispatch pacing; their
    mean is true step time — see dlrover_train_step_seconds)."""

    def __init__(
        self,
        peak_flops: Optional[float] = None,
        window: int = 32,
    ):
        self._peak = peak_flops  # None = ask the device, lazily
        self.flops_per_step: Optional[float] = None
        self._times: collections.deque = collections.deque(maxlen=window)
        self.mfu: Optional[float] = None

    @functools.cached_property
    def peak(self) -> Optional[float]:
        """Peak FLOP/s (imports jax); None off a TPU: no MFU there."""
        if self._peak is not None:
            return self._peak
        return peak_flops_per_s()

    def set_flops(self, flops_per_step: Optional[float]) -> None:
        if not flops_per_step or flops_per_step <= 0:
            return
        self.flops_per_step = float(flops_per_step)
        _FLOPS_PER_STEP.set(self.flops_per_step)

    def observe_step(self, step_seconds: float) -> Optional[float]:
        """Fold one measured step; returns (and gauges) the updated
        windowed MFU, or None until FLOPs are known."""
        if step_seconds > 0:
            self._times.append(float(step_seconds))
        if self.flops_per_step is None or not self._times:
            return None
        mean = sum(self._times) / len(self._times)
        if mean <= 0 or self.peak is None:
            return None
        self.mfu = self.flops_per_step / (mean * self.peak)
        _MFU.set(self.mfu)
        return self.mfu


class StepPhaseProfiler:
    """Per-step wall-time attribution + on-demand N-step capture.

    The owning loop reports what it knows::

        prof.note_data_wait(dt)         # blocked on next(batches)
        prof.note_dispatch(dt, compiled)  # from the trainer's step
        prof.end_step()                 # once per optimizer step

    ``end_step`` measures the step's total wall time on its own
    (injectable) clock and books the residual — wall minus the noted
    phases — as ``device_execute``: in a zero-sync loop that residual
    is exactly the time the host spent ahead of (or waiting on) the
    device. The five phases therefore partition wall time exactly.

    Capture protocol: every ``end_step`` polls the request file
    (mtime-gated, so the steady-state cost is one ``stat``); a fresh
    request arms an N-step capture whose per-step breakdowns fold
    into a digest written to the digest file (and, when a trace dir
    is requested, brackets the steps with ``jax.profiler``).
    """

    def __init__(
        self,
        fn_name: str = "train_step",
        clock: Callable[[], float] = time.perf_counter,
        mfu: Optional[MfuMeter] = None,
        compile_tracker: Optional[CompileTracker] = None,
        request_file: Optional[str] = None,
        digest_file: Optional[str] = None,
        poll_requests: bool = True,
        beacon: object = "auto",
    ):
        self.fn_name = fn_name
        self._clock = clock
        self.mfu = mfu
        self.compile_tracker = compile_tracker
        self._request_file = request_file or profile_request_file()
        self._digest_file = digest_file or profile_digest_file()
        self._poll_requests = poll_requests
        # Stall-localization beacon: the profiler stamps every phase
        # boundary the loop already reports, so cross-host progress
        # comparison costs the hot path one mmap memcpy per note.
        # "auto" = job-scoped beacon unless DLROVER_TPU_BEACON=0;
        # pass None/False to run beacon-less, or inject an instance.
        if beacon == "auto":
            beacon = default_beacon()
        self.beacon: Optional[ProgressBeacon] = beacon or None
        self._step_start: Optional[float] = None
        self._noted: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.steps = 0
        # capture state
        self._capture: Optional[dict] = None
        self._last_request_mtime: Optional[int] = None
        self._last_request_id: Optional[str] = None

    # -- per-step notes ---------------------------------------------------

    def note_data_wait(
        self, seconds: float, h2d_seconds: float = 0.0
    ) -> None:
        """Input wait for this step: ``seconds`` of host-side wait
        (pull/collate/queue) plus ``h2d_seconds`` of host->device
        staging (the split an input pipeline reports via
        ``wait_breakdown()``). Callers without the split pass the
        whole wait as ``seconds`` — attribution stays exhaustive
        either way."""
        host = max(seconds, 0.0)
        h2d = max(h2d_seconds, 0.0)
        if self._step_start is None:
            self._step_start = self._clock() - (host + h2d)
        self._noted["data_wait"] += host
        self._noted["h2d_stage"] += h2d
        if self.beacon is not None:
            self.beacon.stamp(step=self.steps + 1, phase="data_wait")

    def note_dispatch(self, seconds: float, compiled: bool = False) -> None:
        if self._step_start is None:
            self._step_start = self._clock() - max(seconds, 0.0)
        phase = "compile" if compiled else "dispatch"
        self._noted[phase] += max(seconds, 0.0)
        if self.beacon is not None:
            self.beacon.stamp(step=self.steps + 1, phase=phase)

    def end_step(self) -> Dict[str, float]:
        """Close the step: attribute its wall time and return the
        breakdown ``{phase: seconds, "wall_s": total}``."""
        now = self._clock()
        start = self._step_start if self._step_start is not None else now
        wall = max(now - start, 0.0)
        noted = sum(self._noted.values())
        breakdown = dict(self._noted)
        breakdown["device_execute"] = max(wall - noted, 0.0)
        # Clock skew guard: noted phases can (rarely) overshoot the
        # wall clock by scheduler jitter; scale them down so the
        # partition invariant (sum == wall) holds.
        if noted > wall > 0:
            scale = wall / noted
            for k in ("data_wait", "h2d_stage", "compile", "dispatch"):
                breakdown[k] *= scale
            breakdown["device_execute"] = 0.0
        for phase in PHASES:
            if breakdown[phase] > 0:
                _PHASE_SECONDS.inc(breakdown[phase], phase=phase)
        self.steps += 1
        self._noted = dict.fromkeys(PHASES, 0.0)
        self._step_start = now
        breakdown["wall_s"] = wall
        if self.beacon is not None:
            self.beacon.stamp(step=self.steps, phase="device_execute")
        mfu = None
        if self.mfu is not None:
            # Compile-tainted steps stay OUT of the MFU window (same
            # exclusion the profiler-less trainer path applies to its
            # compile-boundary sample): one multi-second XLA compile
            # in a 32-sample mean would underreport utilisation for
            # the whole window — exactly when a straggler-triggered
            # PROFILE is most likely to read it.
            if breakdown["compile"] > 0:
                mfu = self.mfu.mfu
            else:
                mfu = self.mfu.observe_step(wall)
        obs_event(
            "trainer.step_phases",
            step=self.steps,
            wall_s=round(wall, 6),
            data_wait_s=round(breakdown["data_wait"], 6),
            h2d_s=round(breakdown["h2d_stage"], 6),
            compile_s=round(breakdown["compile"], 6),
            dispatch_s=round(breakdown["dispatch"], 6),
            device_s=round(breakdown["device_execute"], 6),
            **({"mfu": round(mfu, 4)} if mfu is not None else {}),
        )
        if self._capture is not None:
            self._capture_step(breakdown)
        if self._poll_requests:
            self.poll_request()
        return breakdown

    # -- on-demand capture ------------------------------------------------

    @property
    def capturing(self) -> bool:
        return self._capture is not None

    def poll_request(self) -> bool:
        """Arm a capture when a fresh request file appeared. Steady-
        state cost: one stat() per step."""
        if self._capture is not None:
            return False
        try:
            mtime = os.stat(self._request_file).st_mtime_ns
        except OSError:
            return False
        if mtime == self._last_request_mtime:
            return False
        self._last_request_mtime = mtime
        try:
            with open(self._request_file) as f:
                req = json.load(f)
        except (OSError, ValueError):
            return False
        if not isinstance(req, dict):
            return False
        req_id = str(req.get("id", ""))
        if not req_id or req_id == self._last_request_id:
            return False
        self._last_request_id = req_id
        self.start_capture(
            steps=int(req.get("steps", 0) or DEFAULT_PROFILE_STEPS),
            trace_dir=str(req.get("trace_dir", "") or ""),
            request_id=req_id,
        )
        return True

    def start_capture(
        self,
        steps: int = DEFAULT_PROFILE_STEPS,
        trace_dir: str = "",
        request_id: str = "",
    ) -> None:
        """Record the next ``steps`` step breakdowns into a digest."""
        if self._capture is not None:
            return
        self._capture = {
            "id": request_id,
            "want": max(int(steps), 1),
            "rows": [],
            "compiles_at_start": (
                self.compile_tracker.compiles
                if self.compile_tracker is not None
                else 0
            ),
            "trace_dir": trace_dir,
            "tracing": False,
        }
        if trace_dir:
            try:
                import jax.profiler

                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
                self._capture["tracing"] = True
            except Exception:  # noqa: BLE001 — a broken trace backend
                # must not block the phase capture
                logger.warning(
                    "jax.profiler trace unavailable; capturing "
                    "phases only", exc_info=True,
                )
        obs_event(
            "trainer.profile_start",
            steps=self._capture["want"],
            request_id=request_id,
        )

    def _capture_step(self, breakdown: Dict[str, float]) -> None:
        cap = self._capture
        cap["rows"].append(breakdown)
        if len(cap["rows"]) >= cap["want"]:
            self._finish_capture()

    def _finish_capture(self) -> dict:
        cap, self._capture = self._capture, None
        if cap["tracing"]:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                logger.warning("stop_trace failed", exc_info=True)
        rows: List[Dict[str, float]] = cap["rows"]
        n = len(rows)
        walls = sorted(r["wall_s"] for r in rows)
        phases = {}
        for phase in PHASES:
            total = sum(r[phase] for r in rows)
            phases[phase] = {
                "total_s": round(total, 6),
                "mean_s": round(total / n, 6) if n else 0.0,
            }
        digest = {
            "id": cap["id"],
            "fn": self.fn_name,
            "steps": n,
            "phases": phases,
            "step_time_mean_s": round(sum(walls) / n, 6) if n else 0.0,
            "step_time_min_s": round(walls[0], 6) if walls else 0.0,
            "step_time_max_s": round(walls[-1], 6) if walls else 0.0,
            "compiles_during_capture": (
                self.compile_tracker.compiles - cap["compiles_at_start"]
                if self.compile_tracker is not None
                else 0
            ),
            "mfu": (
                round(self.mfu.mfu, 4)
                if self.mfu is not None and self.mfu.mfu is not None
                else None
            ),
            "flops_per_step": (
                self.mfu.flops_per_step if self.mfu is not None else None
            ),
            "trace_dir": cap["trace_dir"],
            "ts": time.time(),
        }
        try:
            tmp = f"{self._digest_file}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(digest, f)
            os.replace(tmp, self._digest_file)
        except OSError:
            logger.warning(
                "could not write profile digest %s",
                self._digest_file, exc_info=True,
            )
        _PROFILE_CAPTURES.inc()
        obs_event(
            "trainer.profile_done",
            steps=n,
            request_id=cap["id"],
            mfu=digest["mfu"],
        )
        return digest
