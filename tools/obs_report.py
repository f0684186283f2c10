"""Render a recovery timeline + metrics table from an obs JSONL trace.

Reads the event file the tracer exports (DLROVER_TPU_TRACE_FILE) —
e.g. the per-host traces the chaos drills leave behind — and prints:

* the reconstructed recovery timeline (obs/timeline.py), when the
  canonical trainer marks are present;
* an input-pipeline summary (data-wait vs staging vs train wall
  time) when trainer.prefetch_* events are present — the quick "is
  the prefetch pipeline hiding input staging" check
  (docs/PERFORMANCE.md);
* with ``--goodput``: the exhaustive wall-time attribution
  (productive / compile / data_wait / checkpoint / recovery /
  idle_unknown, obs/goodput.py) over the trace window;
* a per-event-name table: count, and for span events total/mean
  duration, sorted by total time.

``--health TARGET`` renders the master's fleet-health plane (score,
active verdicts with their evidence windows, transition history —
obs/health.py) from either a live master (``host:port``, via the
``HealthQueryRequest`` RPC) or a JSON snapshot file
(``HealthMonitor.snapshot()`` shaped). Exits 1 when a critical
verdict is active, so scripts can gate on it like the /healthz
probe.

``--capacity TARGET`` renders the pool's capacity accounting plane
(per-tenant chip-second ledger, goodput-per-chip, preemption /
restore overhead, SLO error budgets with burn-rate alerts —
obs/capacity.py + the slo_burn detector in obs/health.py) from a
live pool master (``host:port``, via the ``CapacityQueryRequest``
RPC) or a JSON snapshot file (``CapacityLedger.snapshot()`` shaped,
optionally with an attached ``slo`` block). Exits 1 while any
tenant's error budget is burning.

``--postmortem DIR`` instead renders a forensics dir (the flight
recorder's ``bundle_*.json`` black-box bundles + ``stacks_*.txt``
faulthandler dumps + any ``*.jsonl`` traces, obs/postmortem.py) into
the "last 60 seconds before failure" report: failure instant,
windowed event tail, recovery timeline + goodput over the window,
then every bundle's per-thread Python stacks and each stacks file's
final dump.

Exit codes — every probe section follows the same contract so
scripts and cron gates can treat any section uniformly:

    rc  meaning                 examples
    --  ----------------------  ------------------------------------
    0   probe passed            healthy fleet; no unhealthy replica;
                                no failed pool job; budgets intact
    1   probe FAILED            --health: critical / failing-
                                probation / unknown-remediation
                                verdict active; --serving: unhealthy
                                replica; --pool: failed job;
                                --capacity: SLO budget burning;
                                --trace: key not found
    2   target unreachable      snapshot file missing, RPC refused /
                                timed out — the probe itself could
                                not run (distinct from "ran and
                                failed" so alerting can separate
                                outage-of-signal from bad signal)

Usage:
    python tools/obs_report.py TRACE.jsonl [--failure-ts T] [--top N]
    python tools/obs_report.py TRACE.jsonl --goodput
    python tools/obs_report.py --health 127.0.0.1:8001
    python tools/obs_report.py --health health_snapshot.json
    python tools/obs_report.py --capacity 127.0.0.1:8001
    python tools/obs_report.py --postmortem /tmp/dlrover_tpu_forensics_job
    python tools/obs_report.py --selftest

``--selftest`` runs the reconstruction + goodput + fleet-aggregation
+ postmortem pipelines on synthetic events/snapshots/bundles and
exits nonzero on any inconsistency — a fast CI smoke with no inputs
(invoked by tests/test_obs.py).
"""

from __future__ import annotations

import argparse
import sys

import _repo_path  # noqa: F401

from dlrover_tpu.obs.goodput import (
    attribute_goodput,
    render_goodput,
)
from dlrover_tpu.obs.timeline import (
    REQUIRED_PHASES,
    load_events,
    reconstruct_recovery_timeline,
    render_timeline,
)


def metrics_table(events, top: int = 15) -> str:
    stats = {}
    for ev in events:
        name = ev.get("name", "?")
        count, total = stats.get(name, (0, 0.0))
        stats[name] = (count + 1, total + float(ev.get("dur_s", 0.0)))
    rows = sorted(
        stats.items(), key=lambda kv: (-kv[1][1], -kv[1][0])
    )[:top]
    lines = [
        f"top {len(rows)} event names (of {len(stats)}):",
        f"  {'event':<32} {'count':>7} {'total_s':>9} {'mean_s':>9}",
    ]
    for name, (count, total) in rows:
        mean = total / count if count else 0.0
        lines.append(
            f"  {name:<32} {count:>7} {total:>9.3f} {mean:>9.4f}"
        )
    return "\n".join(lines)


def input_pipeline_summary(events) -> str:
    """Data-wait vs step time from the trainer.prefetch_* events.

    ``trainer.prefetch_wait`` carries how long the train loop blocked
    on the input queue per batch; ``trainer.prefetch_stage`` carries
    the worker-side collate+H2D staging cost the pipeline is hiding.
    With ``trainer.step`` events present, the wait is also put in
    proportion to the training wall time. Returns "" when the trace
    has no prefetch events (prefetch off or pre-pipeline trace).
    """
    wait_events = [
        e for e in events if e.get("name") == "trainer.prefetch_wait"
    ]
    waits = [float(e.get("dur_s", 0.0)) for e in wait_events]
    stages = [
        float(e.get("dur_s", 0.0))
        for e in events
        if e.get("name") == "trainer.prefetch_stage"
    ]
    h2ds = [
        float(e.get("dur_s", 0.0))
        for e in events
        if e.get("name") == "trainer.prefetch_h2d"
    ]
    if not waits and not stages and not h2ds:
        return ""
    lines = ["input pipeline (trainer.prefetch_*):"]
    wait_total = sum(waits)
    stage_total = sum(stages)
    h2d_total = sum(h2ds)
    if waits:
        lines.append(
            f"  data-wait : {wait_total:9.3f}s total over "
            f"{len(waits)} batches (mean {wait_total / len(waits):.4f}s)"
        )
        # Host-wait vs H2D-stage split (carried per wait event since
        # the device-resident pipeline): where the blocked time went.
        host_w = sum(float(e.get("host_s", 0.0)) for e in wait_events)
        h2d_w = sum(float(e.get("h2d_s", 0.0)) for e in wait_events)
        if host_w or h2d_w:
            lines.append(
                f"  wait split: host {host_w:.3f}s / "
                f"h2d {h2d_w:.3f}s"
            )
    if stages:
        lines.append(
            f"  staging   : {stage_total:9.3f}s total over "
            f"{len(stages)} batches (mean "
            f"{stage_total / len(stages):.4f}s, overlapped with compute)"
        )
    if h2ds:
        lines.append(
            f"  h2d stage : {h2d_total:9.3f}s total over "
            f"{len(h2ds)} batches (mean "
            f"{h2d_total / len(h2ds):.4f}s)"
        )
    if waits and (stages or h2ds):
        lines.append(
            f"  hidden    : "
            f"{max(stage_total + h2d_total - wait_total, 0.0):9.3f}s "
            "of staging overlapped behind compute"
        )
    step_ts = sorted(
        e["ts"]
        for e in events
        if e.get("name") == "trainer.step" and "ts" in e
    )
    if waits and len(step_ts) >= 2:
        span = step_ts[-1] - step_ts[0]
        if span > 0:
            lines.append(
                f"  train span: {span:9.3f}s across "
                f"{len(step_ts)} steps -> data-wait is "
                f"{100.0 * wait_total / span:.1f}% of wall time"
            )
    return "\n".join(lines)


def perf_summary(events) -> str:
    """Step-phase / compile / MFU summary from the profiler's trace
    events (``trainer.step_phases`` per step, ``trainer.compile`` per
    detected (re)compile, ``trainer.profile_done`` per on-demand
    capture). Returns "" when the trace carries none — a pre-profiler
    trace renders exactly as before."""
    rows = [e for e in events if e.get("name") == "trainer.step_phases"]
    compiles = [e for e in events if e.get("name") == "trainer.compile"]
    captures = [
        e for e in events if e.get("name") == "trainer.profile_done"
    ]
    if not rows and not compiles:
        return ""
    lines = ["step phases (trainer.step_phases):"]
    if rows:
        wall = sum(float(e.get("wall_s", 0.0)) for e in rows)
        lines.append(
            f"  {len(rows)} steps, {wall:.3f}s wall"
            + (
                f", mean step {wall / len(rows):.4f}s"
                if rows
                else ""
            )
        )
        lines.append(
            f"  {'phase':<16} {'total_s':>9} {'mean_s':>9} {'% wall':>7}"
        )
        for phase, key in (
            ("data_wait", "data_wait_s"),
            ("h2d_stage", "h2d_s"),
            ("compile", "compile_s"),
            ("dispatch", "dispatch_s"),
            ("device_execute", "device_s"),
        ):
            total = sum(float(e.get(key, 0.0)) for e in rows)
            pct = 100.0 * total / wall if wall > 0 else 0.0
            lines.append(
                f"  {phase:<16} {total:>9.3f} "
                f"{total / len(rows):>9.4f} {pct:>6.1f}%"
            )
        mfus = [
            float(e["mfu"]) for e in rows if e.get("mfu") is not None
        ]
        if mfus:
            lines.append(
                f"  mfu: last {mfus[-1]:.4f} over {len(mfus)} samples"
            )
    if compiles:
        by_fn = {}
        for e in compiles:
            fn = str(e.get("fn", "?"))
            count, total = by_fn.get(fn, (0, 0.0))
            by_fn[fn] = (count + 1, total + float(e.get("dur_s", 0.0)))
        parts = ", ".join(
            f"{fn} x{c} ({t:.2f}s)"
            for fn, (c, t) in sorted(by_fn.items())
        )
        lines.append(f"  compiles: {parts}")
        # A recompile says what JAX did in it (obs.profiling's
        # listeners): a retrace, a cold compile or a cache load.
        for e in compiles:
            if int(e.get("total", 1)) > 1 and "trace_s" in e:
                stages = " + ".join(
                    f"{stage} {float(e.get(stage + '_s', 0.0)):.2f}"
                    for stage in ("trace", "lower", "backend_compile",
                                  "cache_load")
                )
                lines.append(
                    f"  {e.get('fn', '?')} #{e['total']}: "
                    f"{float(e.get('dur_s', 0.0)):.2f}s = {stages}"
                    + (" (cache hit)" if e.get("cache_hit") else "")
                )
    for e in captures:
        lines.append(
            f"  profile capture: {e.get('steps')} steps"
            f" (request {e.get('request_id') or '-'}"
            + (
                f", mfu {e['mfu']}"
                if e.get("mfu") is not None
                else ""
            )
            + ")"
        )
    return "\n".join(lines)


def report(
    path: str, failure_ts=None, top: int = 15, goodput: bool = False,
    perf: bool = False,
) -> int:
    events = [e for e in load_events(path) if "ts" in e]
    if not events:
        print(f"no events in {path}")
        return 1
    t0 = min(e["ts"] for e in events)
    t1 = max(e["ts"] for e in events)
    print(
        f"{len(events)} events over {t1 - t0:.1f}s from {path}"
    )
    tl = reconstruct_recovery_timeline(events, t_failure=failure_ts)
    if tl is not None:
        print()
        print(render_timeline(tl))
    pipeline = input_pipeline_summary(events)
    if pipeline:
        print()
        print(pipeline)
    if perf:
        summary = perf_summary(events)
        print()
        print(summary or "no perf events (trainer.step_phases) in trace")
    if goodput:
        gp = attribute_goodput(events)
        if gp is not None:
            print()
            print(render_goodput(gp))
    print()
    print(metrics_table(events, top=top))
    return 0


def health_report(target: str) -> int:
    """Render the fleet-health plane (and the remediation engine's
    decision history) from a live master (host:port,
    HealthQueryRequest + RemediationQueryRequest RPCs) or a JSON
    snapshot file (optionally carrying a ``remediation`` key).
    Returns 1 when a critical verdict is active OR a remediation
    probation window is currently failing (probe semantics), else
    0."""
    import dataclasses
    import json
    import os

    from dlrover_tpu.master.remediation import render_remediation
    from dlrover_tpu.obs.health import SEVERITY_CRITICAL, render_health

    remediation_unknown = False
    if os.path.isfile(target):
        with open(target) as f:
            payload = json.load(f)
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        # Looks like a snapshot path, not host:port — a typo'd file
        # name must fail fast, not hang in gRPC connect retries
        # against a nonsense address.
        print(f"health snapshot not found: {target}", file=sys.stderr)
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            # Probe semantics: a down master must fail fast, not
            # ride out the supervisor's full reconnect budget.
            resp = client.query_health(
                include_history=True, max_wait=15.0
            )
        except Exception as exc:  # noqa: BLE001
            print(
                f"health query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        payload = {
            "score": resp.score,
            "active": [
                dataclasses.asdict(v) for v in resp.verdicts
            ],
            "history": [
                dataclasses.asdict(v) for v in resp.history
            ],
        }
        try:
            rem = client.query_remediation(max_wait=15.0)
            payload["remediation"] = {
                "enabled": rem.enabled,
                "dry_run": rem.dry_run,
                "cordoned": list(rem.cordoned),
                "probation_failing": rem.probation_failing,
                "decisions": [
                    dataclasses.asdict(d) for d in rem.decisions
                ],
            }
        except Exception as e:  # noqa: BLE001
            if (
                "no get handler" in str(e)
                or "unknown message" in str(e)
            ):
                # Genuinely pre-remediation master (older wire
                # schema): its health plane still renders and the
                # probe follows the health verdicts alone.
                print(
                    "warning: master predates the remediation "
                    f"RPC: {e}",
                    file=sys.stderr,
                )
            else:
                # A remediation-CAPABLE master failed the query
                # (timeout, transient RPC error): the probe must NOT
                # read healthy — a failing probation could be hiding
                # behind the failure, and the documented exit-1
                # contract would be silently broken.
                print(
                    f"error: remediation query failed: {e}",
                    file=sys.stderr,
                )
                remediation_unknown = True
    print(render_health(payload))
    remediation = payload.get("remediation")
    if remediation is not None:
        print()
        print(render_remediation(remediation))
    critical = sum(
        1
        for v in payload.get("active", [])
        if v.get("severity") == SEVERITY_CRITICAL
    )
    probation_failing = bool(
        (remediation or {}).get("probation_failing")
    )
    return (
        1 if critical or probation_failing or remediation_unknown
        else 0
    )


def serving_report(target: str) -> int:
    """Render the serving plane (router request counters, per-replica
    TTFT/TPOT/queue/KV stats, unhealthy replicas) from a live master
    (host:port, ``ServeQueryRequest`` RPC) or a JSON snapshot file
    (``ServingRouter.snapshot()`` shaped). Exits 1 when any replica is
    currently unhealthy (probe semantics, like ``--health``)."""
    import json
    import os

    from dlrover_tpu.serving.router import render_serving

    if os.path.isfile(target):
        with open(target) as f:
            payload = json.load(f)
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        print(
            f"serving snapshot not found: {target}", file=sys.stderr
        )
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            resp = client.query_serving(max_wait=15.0)
        except Exception as exc:  # noqa: BLE001
            print(
                f"serving query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        finally:
            client.close()
        if not resp.enabled:
            print("serving plane disabled on this master")
            return 0
        payload = resp.snapshot
    print(render_serving(payload))
    return 1 if payload.get("unhealthy") else 0


def pool_report(target: str) -> int:
    """Render the multi-job pool plane (queue depth per priority
    band, per-tenant quota usage, slice utilization, preemption
    counts, wait-time percentiles) from a live pool master
    (host:port, ``PoolQueryRequest`` RPC) or a JSON snapshot file
    (``PoolScheduler.snapshot()`` shaped)."""
    import json
    import os

    from dlrover_tpu.pool.scheduler import render_pool

    if os.path.isfile(target):
        with open(target) as f:
            payload = json.load(f)
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        print(
            f"pool snapshot not found: {target}", file=sys.stderr
        )
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            resp = client.query_pool(max_wait=15.0)
        except Exception as exc:  # noqa: BLE001
            print(
                f"pool query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        finally:
            client.close()
        if not resp.enabled:
            print("pool plane disabled on this master")
            return 0
        payload = resp.snapshot
    print(render_pool(payload))
    failed = [
        jid
        for jid, j in (payload.get("jobs") or {}).items()
        if j.get("state") == "failed"
    ]
    return 1 if failed else 0


def capacity_report(target: str) -> int:
    """Render the pool capacity plane (per-tenant chip-second
    ledger, goodput-per-chip, overhead, SLO error budgets) from a
    live pool master (host:port, ``CapacityQueryRequest`` RPC) or a
    JSON snapshot file (``CapacityLedger.snapshot()`` shaped).
    Exits 1 while any tenant's error budget is burning."""
    import json
    import os

    from dlrover_tpu.obs.capacity import render_capacity

    if os.path.isfile(target):
        with open(target) as f:
            payload = json.load(f)
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        print(
            f"capacity snapshot not found: {target}", file=sys.stderr
        )
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            resp = client.query_capacity(max_wait=15.0)
        except Exception as exc:  # noqa: BLE001
            print(
                f"capacity query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        finally:
            client.close()
        if not resp.enabled:
            print("capacity plane disabled on this master")
            return 0
        payload = resp.snapshot
    print(render_capacity(payload))
    budgets = (payload.get("slo") or {}).get("budgets") or []
    burning = [b for b in budgets if b.get("burning")]
    return 1 if burning else 0


def stall_report(target: str) -> int:
    """Render the stall-localization plane (per-host progress-beacon
    table, open/recent ``collective_stall`` incidents with culprit,
    trace id, and coordinated-capture bundle paths) from a live
    master (host:port, ``StallQueryRequest`` RPC) or a JSON snapshot
    file (``StallCorrelator.snapshot()`` shaped). Exits 1 while an
    incident is open — a paging surface, like --capacity's burning
    budgets."""
    import json
    import os

    from dlrover_tpu.obs.stall import render_stall

    if os.path.isfile(target):
        with open(target) as f:
            payload = json.load(f)
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        print(
            f"stall snapshot not found: {target}", file=sys.stderr
        )
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            resp = client.query_stall(max_wait=15.0)
        except Exception as exc:  # noqa: BLE001
            print(
                f"stall query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        finally:
            client.close()
        if not resp.enabled:
            print("stall plane disabled on this master")
            return 0
        payload = resp.snapshot
    print(render_stall(payload))
    return 1 if payload.get("incident") else 0


def trace_report(key: str, target: str) -> int:
    """Render causal trace timelines for ``key`` — a trace id, a
    serving request id, or a node subject (``node:<id>`` or a bare
    node id) — from a live master (host:port, ``TraceQueryRequest``
    RPC) or a JSON file of trace-store timelines. The span tree is
    indented by causality with per-span durations; requeue hops and
    remediation rungs are summarized per trace."""
    import json
    import os

    from dlrover_tpu.obs.trace_store import render_trace

    def _matches(tl: dict) -> bool:
        subjects = set(tl.get("subjects", ()))
        return (
            tl.get("trace_id") == key
            or key in subjects
            or f"node:{key}" in subjects
        )

    if os.path.isfile(target):
        with open(target) as f:
            doc = json.load(f)
        timelines = doc.get("traces", doc) if isinstance(
            doc, dict
        ) else doc
        timelines = [tl for tl in timelines if _matches(tl)]
    elif (
        target.endswith(".json")
        or os.sep in target
        or ":" not in target
    ):
        print(f"trace snapshot not found: {target}", file=sys.stderr)
        return 2
    else:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(target, node_id=-1)
        try:
            resp = client.query_traces(trace_id=key, max_wait=15.0)
            if resp.enabled and not resp.traces:
                # Not a trace id: try it as a subject (request id /
                # node) — bare node ids get the node: prefix form too.
                resp = client.query_traces(subject=key, max_wait=15.0)
                if not resp.traces and key.isdigit():
                    resp = client.query_traces(
                        subject=f"node:{key}", max_wait=15.0
                    )
        except Exception as exc:  # noqa: BLE001
            print(
                f"trace query to {target} failed: {exc}",
                file=sys.stderr,
            )
            return 2
        finally:
            client.close()
        if not resp.enabled:
            print("trace store disabled on this master")
            return 0
        timelines = list(resp.traces)
    if not timelines:
        print(f"no trace found for {key!r}")
        return 1
    for tl in timelines:
        print(render_trace(tl))
        names = [s.get("name", "") for s in tl.get("spans", ())]
        hops = sum(1 for n in names if n == "serve.hop")
        requeues = sum(1 for n in names if n == "serve.requeue")
        rungs = sorted(
            {
                n.split(".", 1)[1]
                for n in names
                if n.startswith("remediation.")
                and n not in (
                    "remediation.decision", "remediation.verdict",
                    "remediation.governors",
                )
            }
        )
        summary = []
        if hops:
            summary.append(
                f"{hops} replica hop(s), {requeues} requeue(s)"
            )
        if rungs:
            summary.append(f"remediation: {' -> '.join(rungs)}")
        if summary:
            print("  -- " + "; ".join(summary))
    return 0


def _selftest_trace() -> list:
    """Trace assembly hermetically: a synthetic serving-request
    timeline (two hops, phase spans) plus a remediation decision
    trace must render as an indented causal tree through the same
    path ``--trace`` uses."""
    import json as _json
    import tempfile

    from dlrover_tpu.obs.trace_store import (
        TraceStore,
        render_trace,
        span_tree,
    )

    errors = []
    store = TraceStore()
    t = 2000.0
    tid, root = "t" * 32, "r" * 16
    store.add_span(
        tid, "serve.request", t, 3.0, span_id=root,
        request_id="req-1", requeues=1, outcome="done",
    )
    store.add_span(
        tid, "serve.queue", t, 0.1, parent_span_id=root,
        request_id="req-1", hop=0,
    )
    store.add_span(
        tid, "serve.hop", t + 0.1, 1.0, span_id="h" * 16,
        parent_span_id=root, request_id="req-1",
        replica_id=4000000, end="requeue",
    )
    store.add_span(
        tid, "serve.hop", t + 1.3, 1.7, span_id="g" * 16,
        parent_span_id=root, request_id="req-1",
        replica_id=4000001, end="done",
    )
    for i, (name, dur) in enumerate(
        (
            ("serve.dispatch", 0.1),
            ("serve.prefill", 0.5),
            ("serve.first_token", 0.05),
            ("serve.decode", 1.0),
        )
    ):
        store.add_span(
            tid, name, t + 1.35 + 0.4 * i, dur,
            parent_span_id="g" * 16, request_id="req-1",
        )
    dec_tid = "d" * 32
    store.add_span(
        dec_tid, "remediation.decision", t + 1.0,
        span_id="q" * 16, node_id=4000000, decision_id=1,
    )
    store.add_span(
        dec_tid, "remediation.verdict", t + 1.0,
        parent_span_id="q" * 16, node_id=4000000,
        detector="replica_unhealthy",
    )
    store.add_span(
        dec_tid, "remediation.drain_replica", t + 1.1,
        parent_span_id="q" * 16, node_id=4000000,
    )
    store.add_span(
        dec_tid, "serve.requeue", t + 1.1,
        parent_span_id="q" * 16, request_id="req-1",
        link_trace_id=tid,
    )
    tl = store.get(tid)
    if tl is None:
        return ["trace store lost the request trace"]
    tree = span_tree(tl)
    if tree[0]["name"] != "serve.request" or tree[0]["depth"] != 0:
        errors.append(f"tree root wrong: {tree[0]}")
    depths = {s["name"]: s["depth"] for s in tree}
    if depths.get("serve.hop") != 1 or depths.get(
        "serve.prefill"
    ) != 2:
        errors.append(f"tree depths wrong: {depths}")
    rendered = render_trace(tl)
    for needle in ("serve.request", "serve.hop", "req-1"):
        if needle not in rendered:
            errors.append(
                f"trace render missing {needle!r}: {rendered!r}"
            )
    # query surfaces: by subject (request id and node form)
    if not store.query(subject="req-1"):
        errors.append("subject query by request id found nothing")
    if [
        x["trace_id"] for x in store.query(subject="node:4000000")
    ] != [tid, dec_tid]:
        errors.append("subject query by node wrong")
    # file path end to end (the --trace target contract)
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as f:
        _json.dump({"traces": store.query()}, f)
        path = f.name
    try:
        if trace_report(tid, path) != 0:
            errors.append("trace_report rc != 0 on the request trace")
        if trace_report("node:4000000", path) != 0:
            errors.append("trace_report rc != 0 on the node subject")
        if trace_report("missing", path) != 1:
            errors.append("trace_report rc != 1 on an unknown key")
    finally:
        import os as _os

        _os.unlink(path)
    # bounded retention: the store must evict oldest-first
    small = TraceStore(max_traces=3)
    for i in range(10):
        small.add_span(f"trace-{i}", "serve.request", float(i), 1.0)
    if len(small) != 3 or small.get("trace-0") is not None:
        errors.append("trace retention not bounded")
    return errors


def _selftest_serving() -> list:
    """Serving plane hermetically: a fake-clock router over two
    replicas — one serving, one stalling mid-flight — must requeue
    the stalled replica's work on drain, flag it unhealthy, and
    render counters/percentiles via the same path ``--serving``
    uses."""
    import json as _json
    import tempfile

    from dlrover_tpu.serving.router import ServingRouter, render_serving

    errors = []
    clk = [1000.0]
    router = ServingRouter(
        clock=lambda: clk[0],
        config={"progress_timeout_s": 5.0, "latency_window": 64},
    )
    router.register_replica(100, addr="rep-a")
    router.register_replica(101, addr="rep-b")
    rids = [
        router.submit([1, 2, 3], max_new_tokens=4) for _ in range(4)
    ]
    if any(r is None for r in rids):
        errors.append(f"submit rejected: {rids}")
    a = router.pull(100, max_items=2)
    b = router.pull(101, max_items=2)
    if len(a) != 2 or len(b) != 2:
        errors.append(f"pull sizes wrong: {len(a)}, {len(b)}")
    clk[0] += 1.0
    for req in a:
        router.complete(
            100, req.request_id, [7, 8, 9, 10],
            ttft_s=0.2, tpot_s=0.01, finish_reason="length",
        )
    # rep-b stalls holding 2 requests past the progress timeout.
    clk[0] += 6.0
    unhealthy = router.unhealthy_replicas()
    if [u["replica_id"] for u in unhealthy] != [101]:
        errors.append(f"unhealthy detection wrong: {unhealthy}")
    requeued = router.drain_replica(101, reason="selftest")
    if requeued != 2:
        errors.append(f"drain requeued {requeued}, want 2")
    redispatch = router.pull(100, max_items=4)
    if len(redispatch) != 2:
        errors.append(
            f"survivor re-pulled {len(redispatch)}, want 2"
        )
    for req in redispatch:
        router.complete(
            100, req.request_id, [1, 1, 1, 1],
            ttft_s=0.3, tpot_s=0.02, finish_reason="length",
            phases={
                "dispatch": 0.05, "prefill": 0.25,
                "first_decode": 0.05, "decode": 0.4,
            },
        )
    counters = router.counters()
    if counters["done"] != 4 or counters["requeued_total"] != 2:
        errors.append(f"counters wrong: {counters}")
    for rid in rids:
        rec = router.result(rid)
        if rec is None or rec["state"] != "done":
            errors.append(f"request {rid} not done: {rec}")
    # Late duplicate from the drained replica: dropped, first wins.
    if router.complete(101, rids[-1], [9, 9, 9, 9]):
        errors.append("late duplicate completion was accepted")
    router.report_stats(
        100,
        {
            "queue_depth": 1, "active": 2, "tokens_generated": 16,
            "ttft_p99_s": 0.25, "tpot_p50_s": 0.015,
            "kv": {"utilization": 0.5},
        },
    )
    snapshot = router.snapshot()
    rendered = render_serving(snapshot)
    for needle in (
        "4 done",
        "2 requeue(s)",
        "replica 100",
        "replica 101",
        "[UNHEALTHY]",
        "kv 50%",
        "UNHEALTHY replicas: [101]",
        # The worst-trace TTFT breakdown: queue covers the 7s the
        # requeued requests waited across the stall + drain.
        "worst TTFT",
        "dispatch 0.050s",
        "prefill 0.250s",
        "1 requeue(s)",
    ):
        if needle not in rendered:
            errors.append(
                f"serving render missing {needle!r}: {rendered!r}"
            )
    # The --serving file path end to end: snapshot -> JSON -> report,
    # rc 1 while the drained replica is still unhealthy.
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as f:
        _json.dump(snapshot, f)
        path = f.name
    try:
        if serving_report(path) != 1:
            errors.append(
                "serving_report rc != 1 with an unhealthy replica"
            )
        # The replica re-registers (fresh process): healthy again.
        router.register_replica(101, addr="rep-b")
        with open(path, "w") as f:
            _json.dump(router.snapshot(), f)
        if serving_report(path) != 0:
            errors.append(
                "serving_report rc != 0 after replica recovery"
            )
    finally:
        import os as _os

        _os.unlink(path)
    errors.extend(_selftest_serving_disagg())
    return errors


def _selftest_serving_disagg() -> list:
    """Disaggregated snapshot hermetically: a prefill + decode fleet
    moves one request through prefilling -> handoff -> decoding ->
    done, and the ``--serving`` renderer shows the per-role rows,
    the staged-handoff queue, and per-role KV utilization."""
    import numpy as np

    from dlrover_tpu.serving import handoff as hmod
    from dlrover_tpu.serving.router import (
        ServingRouter,
        render_serving,
    )

    errors = []
    clk = [2000.0]
    router = ServingRouter(
        clock=lambda: clk[0],
        config={"progress_timeout_s": 5.0},
    )
    router.register_replica(200, addr="pre-a", role="prefill")
    router.register_replica(201, addr="dec-a", role="decode")
    rid = router.submit([1, 2, 3], max_new_tokens=4)
    if router.pull(201, max_items=1) != []:
        errors.append("decode replica was fed a raw prompt")
    items = router.pull(200, max_items=1)
    if not items or router.result(rid)["state"] != "prefilling":
        errors.append(
            f"prefill dispatch wrong: {router.result(rid)}"
        )
    zeros = np.zeros((2, 8, 2, 4), np.float32)
    wire = hmod.pack(
        hmod.HandoffPayload(
            rid, [1, 2, 3], 4, 0.0, 9, zeros, zeros,
            ttft_s=0.1,
            phases={
                "dispatch": 0.0, "prefill": 0.08,
                "first_decode": 0.02,
            },
        )
    )
    router.complete(200, rid, [], handoff=wire)
    if router.result(rid)["state"] != "handoff":
        errors.append(
            f"handoff staging wrong: {router.result(rid)}"
        )
    router.report_stats(
        201,
        {
            "role": "decode", "queue_depth": 0, "active": 1,
            "tokens_generated": 1, "kv": {"utilization": 0.25},
        },
    )
    snapshot = router.snapshot()
    rendered = render_serving(snapshot)
    for needle in (
        "role prefill",
        "role decode",
        "handoff queue 1 staged",
        "kv 25%",
    ):
        if needle not in rendered:
            errors.append(
                f"disagg serving render missing {needle!r}: "
                f"{rendered!r}"
            )
    out = router.pull(201, max_items=1)
    if not out or not out[0].handoff:
        errors.append("decode pull did not carry the KV payload")
    clk[0] += 0.5
    router.complete(
        201, rid, [9, 8, 7, 6], ttft_s=0.1, tpot_s=0.01,
        finish_reason="length",
        phases={
            "dispatch": 0.0, "prefill": 0.08, "first_decode": 0.02,
            "handoff": 0.01, "decode": 0.03,
        },
    )
    rec = router.result(rid)
    if rec["state"] != "done" or "handoff" not in rec["phases"]:
        errors.append(f"disagg completion wrong: {rec}")
    if router.snapshot()["handoff_queue_depth"] != 0:
        errors.append("handoff queue not drained after dispatch")
    return errors


def _selftest_health() -> list:
    """Health plane hermetically: a fake-clock monitor over a ramping
    slow host + a healthy control host must convict exactly the slow
    one, queue a PROFILE action, and render score + evidence via the
    same path ``--health`` uses."""
    import json as _json
    import tempfile

    from dlrover_tpu.obs.health import (
        SEVERITY_CRITICAL,
        HealthMonitor,
        render_health,
    )
    from dlrover_tpu.obs.timeseries import TimeSeriesStore

    errors = []
    clk = [0.0]
    store = TimeSeriesStore(clock=lambda: clk[0])
    actions = []
    monitor = HealthMonitor(
        store,
        action_sink=lambda node, action: actions.append((node, action)),
        clock=lambda: clk[0],
        config={"window_s": 60.0, "min_points": 3.0},
    )
    monitor.fleet = type(
        "F",
        (),
        {
            "node_for_host": staticmethod(
                lambda host: {"slow": 3, "ok": 4}.get(host)
            ),
            "aggregates": staticmethod(dict),
        },
    )()
    for i in range(40):
        t = 900.0 + i * 5
        slow = 0.1 if t < 1000 else 0.1 * (1 + (t - 1000) / 30.0)
        store.record("host.step_time", slow, ts=t, host="slow")
        store.record("host.step_time", 0.1, ts=t, host="ok")
    clk[0] = 1095.0
    verdicts = monitor.evaluate_once()
    convicted = {(v.detector, v.host, v.severity) for v in verdicts}
    if ("throughput_degradation", "slow", SEVERITY_CRITICAL) not in convicted:
        errors.append(f"slow host not convicted: {convicted}")
    if any(v.host == "ok" for v in verdicts):
        errors.append(f"healthy control host convicted: {convicted}")
    if actions != [(3, "profile")]:
        errors.append(f"PROFILE not queued for node 3: {actions}")
    if monitor.health_score() >= 1.0:
        errors.append(f"score did not drop: {monitor.health_score()}")
    payload = monitor.healthz_payload()
    if payload["ok"] or payload["critical_verdicts"] != 1:
        errors.append(f"healthz payload wrong: {payload}")
    rendered = render_health(monitor.snapshot())
    for needle in (
        "job health score 0.70",
        "throughput_degradation",
        "action: profile",
        "evidence",
    ):
        if needle not in rendered:
            errors.append(f"health render missing {needle!r}")
    # The --health file path end to end: snapshot -> JSON -> report,
    # rc 1 because a critical verdict is active.
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as f:
        _json.dump(monitor.snapshot(), f)
        path = f.name
    try:
        if health_report(path) != 1:
            errors.append("health_report rc != 1 with critical verdict")
    finally:
        import os as _os

        _os.unlink(path)
    # Recovery: the slow host heals, the verdict resolves, rc goes 0.
    for i in range(40):
        t = 1100.0 + i * 5
        store.record("host.step_time", 0.1, ts=t, host="slow")
        store.record("host.step_time", 0.1, ts=t, host="ok")
    clk[0] = 1295.0
    if monitor.evaluate_once():
        errors.append("verdict did not resolve after recovery")
    if monitor.health_score() != 1.0:
        errors.append(
            f"score did not recover: {monitor.health_score()}"
        )
    history = monitor.history()
    if not any(v.resolved for v in history):
        errors.append("no resolution transition in history")
    return errors


def _selftest_remediation() -> list:
    """Remediation rendering + probe semantics: the --health body
    must carry the decision history with its governor audit trail,
    and a currently-failing probation window must exit 1 even with no
    critical verdict active (the verdict resolved, but remediation
    demonstrably did not restore health)."""
    import json as _json
    import tempfile

    from dlrover_tpu.master.remediation import render_remediation

    errors = []
    payload = {
        "enabled": True,
        "dry_run": False,
        "cordoned": [1],
        "probation_failing": True,
        "decisions": [
            {
                "decision_id": 1,
                "detector": "throughput_degradation",
                "node_id": 1,
                "host": "h1",
                "action": "cordon_replace",
                "outcome": "acted",
                "dry_run": False,
                "governors": {
                    "hysteresis": "ok", "cooldown": "ok",
                    "blast_radius": "ok", "min_nodes": "ok",
                },
            },
            {
                "decision_id": 2,
                "detector": "data_starvation",
                "node_id": 2,
                "host": "h2",
                "action": "restart_training",
                "outcome": "blocked",
                "dry_run": False,
                "governors": {
                    "hysteresis": "ok",
                    "blast_radius": (
                        "blocked: 1 action(s) in the last 600s "
                        "window (cap 1)"
                    ),
                    "cooldown": "ok",
                },
            },
        ],
    }
    rendered = render_remediation(payload)
    for needle in (
        "remediation (active)",
        "cordoned [1]",
        "PROBATION FAILING",
        "cordon_replace",
        "governors ok:",
        "governor blast_radius: blocked:",
    ):
        if needle not in rendered:
            errors.append(
                f"remediation render missing {needle!r}: {rendered!r}"
            )
    # Probe semantics end to end through the --health file path: no
    # critical verdict, but a failing probation -> rc 1.
    snapshot = {
        "score": 1.0,
        "active": [],
        "history": [],
        "remediation": payload,
    }
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as f:
        _json.dump(snapshot, f)
        path = f.name
    try:
        if health_report(path) != 1:
            errors.append(
                "health_report rc != 1 with a failing probation"
            )
        snapshot["remediation"]["probation_failing"] = False
        with open(path, "w") as f:
            _json.dump(snapshot, f)
        if health_report(path) != 0:
            errors.append(
                "health_report rc != 0 with healthy remediation"
            )
    finally:
        import os as _os

        _os.unlink(path)
    return errors


def selftest() -> int:
    """Hermetic check of the reconstruction pipeline on synthetic
    events shaped like a real drill trace."""
    t = 1000.0
    events = [
        {"name": "node.heartbeat_timeout", "ts": t, "node_id": 1},
        {"name": "trainer.proc_start", "ts": t + 4.0},
        {"name": "trainer.dist_ready", "ts": t + 10.0},
        {"name": "trainer.built", "ts": t + 25.0},
        {"name": "trainer.restore_done", "ts": t + 27.5},
        {"name": "trainer.first_step_done", "ts": t + 40.0},
        {"name": "trainer.step", "ts": t + 41.0, "step": 11},
        {"name": "trainer.throughput_recovered", "ts": t + 45.0},
        # input pipeline shaped like a healthy prefetch: staging cost
        # per batch is high, consumer wait is near zero
        {"name": "trainer.prefetch_start", "ts": t + 40.0, "depth": 2},
        {"name": "trainer.prefetch_stage", "ts": t + 40.1,
         "dur_s": 0.5},
        {"name": "trainer.prefetch_stage", "ts": t + 40.7,
         "dur_s": 0.5},
        {"name": "trainer.prefetch_h2d", "ts": t + 40.6,
         "dur_s": 0.2},
        {"name": "trainer.prefetch_h2d", "ts": t + 41.2,
         "dur_s": 0.2},
        {"name": "trainer.prefetch_wait", "ts": t + 41.0,
         "dur_s": 0.01, "host_s": 0.008, "h2d_s": 0.002},
        {"name": "trainer.prefetch_wait", "ts": t + 42.0,
         "dur_s": 0.03, "host_s": 0.02, "h2d_s": 0.01},
        {"name": "trainer.step", "ts": t + 43.0, "step": 12},
        {"name": "trainer.prefetch_stop", "ts": t + 45.0,
         "delivered": 2, "dropped": 0},
    ]
    tl = reconstruct_recovery_timeline(events)
    errors = []
    if tl is None:
        errors.append("reconstruction returned None")
    else:
        if not tl.complete:
            errors.append(f"timeline incomplete: {tl.phases}")
        for name in REQUIRED_PHASES:
            dur = tl.phases.get(name)
            if dur is None or dur <= 0:
                errors.append(f"phase {name} not positive: {dur}")
        expect = {
            "failure-detect": 4.0,
            "rendezvous": 6.0,
            "build": 15.0,
            "restore": 2.5,
            "first-step": 12.5,
            "throughput-90": 5.0,
        }
        for name, want in expect.items():
            got = tl.phases.get(name)
            if got is None or abs(got - want) > 1e-6:
                errors.append(f"phase {name}: want {want}, got {got}")
        if abs(tl.total_s - 45.0) > 1e-6:
            errors.append(f"total_s: want 45.0, got {tl.total_s}")
        render_timeline(tl)  # must not raise
        metrics_table(events)
        pipeline = input_pipeline_summary(events)
        if "data-wait" not in pipeline:
            errors.append(f"no data-wait line in: {pipeline!r}")
        if "0.040s total over 2 batches" not in pipeline:
            errors.append(f"wrong wait total in: {pipeline!r}")
        if "1.000s total over 2 batches" not in pipeline:
            errors.append(f"wrong stage total in: {pipeline!r}")
        if "0.400s total over 2 batches" not in pipeline:
            errors.append(f"wrong h2d stage total in: {pipeline!r}")
        if "wait split: host 0.028s / h2d 0.012s" not in pipeline:
            errors.append(f"wrong wait split in: {pipeline!r}")
        if "1.360s" not in pipeline:  # hidden = stage + h2d - wait
            errors.append(f"wrong hidden time in: {pipeline!r}")
        if "data-wait is 2.0% of wall time" not in pipeline:
            errors.append(f"wrong wall fraction in: {pipeline!r}")
        if input_pipeline_summary(
            [e for e in events if "prefetch" not in e["name"]]
        ):
            errors.append("pipeline summary not empty without events")
        errors.extend(_selftest_goodput(events))
    errors.extend(_selftest_fleet())
    errors.extend(_selftest_postmortem())
    errors.extend(_selftest_perf())
    errors.extend(_selftest_health())
    errors.extend(_selftest_remediation())
    errors.extend(_selftest_serving())
    errors.extend(_selftest_trace())
    errors.extend(_selftest_pool())
    errors.extend(_selftest_capacity())
    errors.extend(_selftest_stall())
    if errors:
        print("obs selftest FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print("obs selftest ok")
    return 0


def _selftest_pool() -> list:
    """The --pool path end to end: a real PoolScheduler plays a
    priority-preemption + quota story with fake runtimes, its
    snapshot round-trips through JSON, and the renderer surfaces
    queue depth per band, tenant quotas, slice utilization,
    preemptions, and wait percentiles."""
    import json
    import os
    import tempfile

    from dlrover_tpu.pool import (
        JobRuntime,
        PoolJobSpec,
        PoolScheduler,
        SlicePool,
    )
    from dlrover_tpu.pool.scheduler import render_pool

    errors = []

    class FakeRT(JobRuntime):
        def place(self, slices, resume):
            pass

        def park(self, on_parked):
            on_parked({"staged": True, "path": "/ck", "step": 3})

        def stop(self):
            pass

    pool = SlicePool(4, tenant_quotas={"research": 2})
    sched = PoolScheduler(pool, park_timeout_s=5.0)
    sched.submit(
        PoolJobSpec(job_id="low", tenant="research", priority=1,
                    n_slices=2, min_slices=1),
        FakeRT(),
    )
    sched.submit(
        PoolJobSpec(job_id="high", tenant="prod", priority=5,
                    n_slices=4),
        FakeRT(),
    )
    # low was preempted for high; a second research job is now
    # quota-feasible but capacity-queued.
    sched.submit(
        PoolJobSpec(job_id="more", tenant="research", priority=1,
                    n_slices=1),
        FakeRT(),
    )
    info = sched.job_info("low")
    if info["state"] != "preempted" or info["preemptions"] != 1:
        errors.append(f"pool selftest: low not preempted: {info}")
    if sched.job_info("high")["slices"] != [0, 1, 2, 3]:
        errors.append("pool selftest: high gang not whole")
    snap = sched.snapshot()
    if snap["counters"]["preemptions"].get("priority") != 1:
        errors.append(
            f"pool selftest: counters {snap['counters']}"
        )
    if snap["queue_depth"].get("1") != 2:
        errors.append(
            f"pool selftest: band-1 depth {snap['queue_depth']}"
        )
    rendered = render_pool(snap)
    for needle in (
        "utilization 100%",
        "queue depth: 2",
        "research:",
        "priority=1",
        "wait band",
    ):
        if needle not in rendered:
            errors.append(
                f"pool selftest: {needle!r} missing from:\n"
                f"{rendered}"
            )
    # File-target path end to end (the --pool target contract).
    with tempfile.NamedTemporaryFile(
        "w", suffix="_pool.json", delete=False
    ) as f:
        json.dump(snap, f)
        path = f.name
    try:
        if pool_report(path) != 0:
            errors.append("pool selftest: pool_report(file) != 0")
    finally:
        os.unlink(path)
    return errors


def _selftest_capacity() -> list:
    """The --capacity path end to end: a real CapacityLedger plays a
    two-tenant pool story under a fake clock — idle gap, allocation,
    goodput accrual, a preemption + restore — then the snapshot must
    hold the partition invariant exactly, the renderer must surface
    the per-tenant table and SLO budget alerts, and the file-target
    rc contract must distinguish burning (1) / healthy (0) /
    missing (2)."""
    import json
    import os
    import tempfile

    from dlrover_tpu.obs.capacity import (
        CapacityLedger,
        render_capacity,
    )
    from dlrover_tpu.pool.slice_pool import SliceSpec

    errors = []
    t0 = 1000.0
    specs = [SliceSpec(slice_id=0), SliceSpec(slice_id=1)]
    led = CapacityLedger(specs, clock=lambda: t0)  # 2 x 4 chips
    # tenant a trains on both slices after a 10s idle gap, gets
    # preempted at t+40, resumes on slice 1 at t+70.
    led.on_allocate("ja", "a", [0, 1], ts=t0 + 10)
    led.observe_goodput("ja", 0.9, ts=t0 + 40)
    led.mark_preempting("ja", ts=t0 + 40)
    led.on_release("ja", [0, 1], ts=t0 + 50)
    # tenant b serves on slice 0 from t+60.
    led.on_allocate("jb", "b", [0], ts=t0 + 60)
    led.on_allocate("ja", "a", [1], ts=t0 + 70)
    led.mark_restoring("ja", ts=t0 + 70)
    led.job_ready("ja", ts=t0 + 80)
    snap = led.snapshot(ts=t0 + 100)
    if not snap["partition_ok"]:
        errors.append(
            f"capacity selftest: partition broken: "
            f"{snap['chip_seconds']}"
        )
    if abs(snap["chip_seconds"]["capacity"] - 800.0) > 1e-6:
        errors.append(
            f"capacity selftest: capacity "
            f"{snap['chip_seconds']['capacity']} != 800"
        )
    by_state = snap["chip_seconds"]["by_state"]
    if abs(by_state.get("idle", 0.0) - 200.0) > 1e-6:
        errors.append(
            f"capacity selftest: idle cs {by_state.get('idle')}"
            " != 200"
        )
    a = snap["tenants"].get("a", {})
    if abs(a.get("overhead_chip_seconds", 0.0) - 120.0) > 1e-6:
        errors.append(
            f"capacity selftest: tenant-a overhead {a} != 120"
        )
    # A ratio observation applies forward: 0.9 lands at t+40 just
    # as the preemption stops accrual, so only the 20s x 4 chips
    # after the restore completes counts — overhead accrues none.
    if abs(a.get("productive_chip_seconds", 0.0) - 72.0) > 1e-6:
        errors.append(
            f"capacity selftest: tenant-a productive {a} != 72"
        )
    if abs(snap.get("utilization", 0.0) - 0.75) > 1e-6:
        errors.append(
            f"capacity selftest: utilization {snap['utilization']}"
        )
    snap["slo"] = {
        "budgets": [
            {
                "tenant": "b", "slo": "ttft",
                "series": "tenant.ttft_p99_s",
                "objective": 0.5, "direction": "max",
                "budget_remaining": 0.2, "burning": True,
                "severity": "critical",
                "burn": {"fast": 15.4, "slow": 2.0},
            },
        ]
    }
    rendered = render_capacity(snap)
    for needle in (
        "2 slice(s) / 8 chip(s)",
        "utilization 75%",
        "preempting 80.0",
        "restoring 40.0",
        "b/ttft: budget remaining 20%",
        "BURNING [critical] fast 15.4x slow 2.0x",
    ):
        if needle not in rendered:
            errors.append(
                f"capacity selftest: {needle!r} missing from:\n"
                f"{rendered}"
            )
    # File-target rc contract: burning -> 1, healthy -> 0,
    # missing -> 2.
    with tempfile.NamedTemporaryFile(
        "w", suffix="_capacity.json", delete=False
    ) as f:
        json.dump(snap, f)
        path = f.name
    try:
        if capacity_report(path) != 1:
            errors.append(
                "capacity selftest: burning snapshot rc != 1"
            )
        snap["slo"]["budgets"][0]["burning"] = False
        with open(path, "w") as f:
            json.dump(snap, f)
        if capacity_report(path) != 0:
            errors.append(
                "capacity selftest: healthy snapshot rc != 0"
            )
    finally:
        os.unlink(path)
    if capacity_report(path) != 2:
        errors.append("capacity selftest: missing target rc != 2")
    return errors


def _selftest_goodput(events) -> list:
    """Goodput attribution on the same synthetic trace: buckets must
    be exhaustive (sum == window) with the hand-computed values."""
    errors = []
    gp = attribute_goodput(events)
    if gp is None:
        return ["goodput attribution returned None"]
    want = {
        "recovery": 40.0,       # failure t .. first_step_done t+40
        "data_wait": 0.04,      # two prefetch_wait events
        "productive": 1.97,     # steps t+41..t+43 minus data-wait
        "compile": 0.0,
        "checkpoint": 0.0,
        "idle_unknown": 2.99,   # the remainder
    }
    for cat, val in want.items():
        got = gp.seconds.get(cat, 0.0)
        if abs(got - val) > 1e-6:
            errors.append(f"goodput[{cat}]: want {val}, got {got}")
    if abs(sum(gp.seconds.values()) - gp.total_s) > 1e-6:
        errors.append(
            f"goodput buckets sum {sum(gp.seconds.values())} != "
            f"window {gp.total_s}"
        )
    rendered = render_goodput(gp)
    if "goodput" not in rendered or "recovery" not in rendered:
        errors.append(f"goodput render incomplete: {rendered!r}")
    return errors


def _selftest_fleet() -> list:
    """Fleet aggregation on two synthetic host snapshots: host-labeled
    series, cross-host aggregates, and age-out on removal."""
    from types import SimpleNamespace

    from dlrover_tpu.obs.fleet import FleetAggregator
    from dlrover_tpu.obs.metrics import MetricsRegistry

    errors = []
    reg = MetricsRegistry()
    fleet = FleetAggregator(registry=reg, ttl=3600.0)

    def snap(node_id, host, step_time, syncs):
        return SimpleNamespace(
            node_id=node_id,
            host=host,
            timestamp=1000.0,
            registry={
                "dlrover_train_steps_total": {
                    "type": "counter", "help": "steps",
                    "labelnames": [], "series": [[[], 10 + node_id]],
                },
                "dlrover_train_host_syncs_total": {
                    "type": "counter", "help": "syncs",
                    "labelnames": ["reason"],
                    "series": [[["log"], syncs]],
                },
            },
            resource={"tokens_per_s": 1000.0 * (node_id + 1)},
            step_times=[step_time] * 3,
            events=[],
        )

    fleet.ingest(snap(0, "w0", 0.10, 5))
    fleet.ingest(snap(1, "w1", 0.30, 7))
    body = reg.render()
    for needle in (
        'dlrover_train_steps_total{host="w0"} 10',
        'dlrover_train_steps_total{host="w1"} 11',
        'dlrover_train_host_syncs_total{reason="log",host="w1"} 7',
        "dlrover_fleet_hosts 2",
        'dlrover_fleet_series{series="host_syncs_total",stat="sum"} 12',
        'dlrover_fleet_series{series="step_time_s",stat="max"} 0.3',
        'dlrover_fleet_series{series="tokens_per_s",stat="min"} 1000',
    ):
        if needle not in body:
            errors.append(f"fleet render missing {needle!r}")
    fleet.remove_node(1)
    body = reg.render()
    if 'host="w1"' in body:
        errors.append("departed host w1 still rendered after removal")
    if "dlrover_fleet_hosts 1" not in body:
        errors.append("fleet host count did not drop to 1")
    fleet.close()
    if "dlrover_fleet_hosts" in reg.render():
        errors.append("fleet collector still rendering after close()")
    return errors


def _selftest_postmortem() -> list:
    """Postmortem rendering over a synthetic forensics dir: one hang
    bundle with a wedged thread, one faulthandler stacks file, one
    trace — the report must carry the failure instant, the hung
    thread's stack, the fault dump, and the goodput attribution."""
    import json as _json
    import tempfile

    from dlrover_tpu.obs.postmortem import (
        collect_events,
        failure_instant,
        last_fault_dump,
        load_bundles,
        render_postmortem,
    )

    errors = []
    t = 2000.0
    with tempfile.TemporaryDirectory() as dir_:
        bundle = {
            "schema": 1,
            "kind": "hang",
            "reason": "no step progress for 62.0s",
            "ts": t + 62.0,
            "role": "agent",
            "rank": 0,
            "pid": 111,
            "proc": {"python": "3.11.0", "jax_platform": "cpu"},
            "env": {},
            "notes": {"step": 41, "loss": 2.5},
            "logs": [
                {"ts": t + 61.0, "level": "WARNING",
                 "logger": "agent", "msg": "no step progress"},
            ],
            "events": [
                {"name": "trainer.step", "ts": t, "step": 40,
                 "pid": 222},
                {"name": "trainer.step", "ts": t + 1.0, "step": 41,
                 "pid": 222},
                {"name": "stall.incident", "ts": t + 60.0,
                 "pid": 111, "incident": "stall-2060-1",
                 "kind": "laggard", "culprit": "host-c", "hosts": 3},
                {"name": "agent.hang_detected", "ts": t + 62.0,
                 "pid": 111},
            ],
            "metrics": {},
            "stacks": [
                {"thread": "MainThread", "ident": 1, "daemon": False,
                 "current": True,
                 "frames": ["agent.py:500 in _invoke_run"]},
            ],
            "stacks_file": f"{dir_}/stacks_111.txt",
        }
        with open(f"{dir_}/bundle_agent_r0_111_001_hang.json", "w") as f:
            _json.dump(bundle, f)
        with open(f"{dir_}/stacks_222.txt", "w") as f:
            f.write(
                "# flight recorder role=trainer rank=0 pid=222\n"
                "Current thread 0x00007f01 (most recent call first):\n"
                '  File "train.py", line 12 in stuck_collective\n'
                '  File "train.py", line 30 in main\n'
            )
        bundles = load_bundles(dir_)
        if len(bundles) != 1:
            errors.append(f"expected 1 bundle, loaded {len(bundles)}")
        events = collect_events(dir_, bundles)
        if len(events) != 4:
            errors.append(f"expected 4 events, got {len(events)}")
        t_fail, source = failure_instant(events, bundles)
        if t_fail != t + 62.0 or source != "agent.hang_detected":
            errors.append(
                f"failure instant wrong: {t_fail} from {source!r}"
            )
        dump = last_fault_dump(
            open(f"{dir_}/stacks_222.txt").read()
        )
        if not dump.startswith("Current thread"):
            errors.append(f"last_fault_dump wrong: {dump!r}")
        report = render_postmortem(dir_, window=90.0)
        for needle in (
            "failure instant: 2062.000 (from agent.hang_detected)",
            "bundle_agent_r0_111_001_hang.json",
            "notes: loss=2.5, step=41",
            "thread MainThread (current):",
            "agent.py:500 in _invoke_run",
            "stack dump stacks_222.txt (pid 222):",
            "stuck_collective",
            "goodput",  # attribution over the window
            "agent.hang_detected",
            "stall incidents in window:",
            "stall-2060-1 opened at 2060.000: laggard, "
            "culprit host-c, 3 host(s) parked",
        ):
            if needle not in report:
                errors.append(f"postmortem missing {needle!r}")
        empty = render_postmortem(f"{dir_}/nope")
        if "no forensics artifacts" not in empty:
            errors.append(f"empty-dir message wrong: {empty!r}")
    return errors


def _selftest_perf() -> list:
    """--perf section on synthetic profiler events: phase totals,
    wall percentages, compile rollup, and capture line must all be
    hand-verifiable."""
    errors = []
    t = 3000.0
    events = [
        {"name": "trainer.compile", "ts": t, "fn": "train_step",
         "dur_s": 2.0, "total": 1},
        {"name": "trainer.step_phases", "ts": t + 2.0, "step": 1,
         "wall_s": 2.5, "data_wait_s": 0.25, "h2d_s": 0.0,
         "compile_s": 2.0, "dispatch_s": 0.05, "device_s": 0.2},
        {"name": "trainer.step_phases", "ts": t + 3.0, "step": 2,
         "wall_s": 0.5, "data_wait_s": 0.05, "h2d_s": 0.1,
         "compile_s": 0.0, "dispatch_s": 0.05, "device_s": 0.3,
         "mfu": 0.41},
        {"name": "trainer.step_phases", "ts": t + 4.0, "step": 3,
         "wall_s": 1.0, "data_wait_s": 0.2, "h2d_s": 0.1,
         "compile_s": 0.0, "dispatch_s": 0.1, "device_s": 0.6,
         "mfu": 0.43},
        {"name": "trainer.profile_done", "ts": t + 4.0, "steps": 3,
         "request_id": "r1", "mfu": 0.43},
    ]
    summary = perf_summary(events)
    for needle in (
        "3 steps, 4.000s wall",
        "data_wait            0.500",  # 0.25+0.05+0.2
        "h2d_stage            0.200",  # the split's H2D slice
        "compile              2.000",
        "device_execute       1.100",
        "50.0%",   # compile = 2.0 / 4.0 wall
        "mfu: last 0.4300 over 2 samples",
        "compiles: train_step x1 (2.00s)",
        "profile capture: 3 steps (request r1, mfu 0.43)",
    ):
        if needle not in summary:
            errors.append(f"perf summary missing {needle!r}: {summary!r}")
    if perf_summary(
        [e for e in events if "step_phases" not in e["name"]
         and "compile" not in e["name"]]
    ):
        errors.append("perf summary not empty without profiler events")
    return errors


def _selftest_stall() -> list:
    """The --stall path end to end: a real StallCorrelator over a
    fake three-host fleet with an injected clock. One host stops
    stamping -> after the tick streak exactly that host is convicted
    (collective_stall), the coordinated capture reaches all three
    nodes, the incident trace carries per-host progress spans, the
    snapshot round-trips through JSON into stall_report's rc=1 /
    rc=0 contract, and a flapping beacon never convicts."""
    import json
    import os
    import tempfile
    import types

    from dlrover_tpu.obs.stall import StallCorrelator, render_stall

    errors = []
    t = [5000.0]

    class FakeFleet:
        def __init__(self):
            self.snaps = {}

        def set(self, host, node_id, step, phase, mb, age_s):
            self.snaps[host] = types.SimpleNamespace(
                host=host, node_id=node_id, wall_ts=t[0],
                beacon={"step": step, "phase": phase,
                        "microbatch": mb, "age_s": age_s},
            )

        def live_snapshots(self):
            return list(self.snaps.values())

    fleet = FakeFleet()
    pushes = []

    def capture(node_id, action, dedupe_key=None):
        pushes.append((node_id, action, dedupe_key))
        return True

    from dlrover_tpu.obs.trace_store import TraceStore

    traces = TraceStore(clock=lambda: t[0])
    corr = StallCorrelator(
        fleet=fleet, traces=traces, capture=capture,
        clock=lambda: t[0],
        config={"stall_after_s": 60.0, "stall_ticks": 2.0,
                "capture_cooldown_s": 0.0},
    )
    # Healthy fleet: everyone stamping, no verdicts.
    for h, n in (("host-a", 0), ("host-b", 1), ("host-c", 2)):
        fleet.set(h, n, step=10, phase="dispatch", mb=3, age_s=1.0)
    if corr.evaluate():
        errors.append("stall selftest: verdict on a healthy fleet")
    # host-c wedges a step behind; peers park at step 11's dispatch.
    t[0] += 90.0
    fleet.set("host-a", 0, 11, "dispatch", -1, 90.0)
    fleet.set("host-b", 1, 11, "dispatch", -1, 90.0)
    fleet.set("host-c", 2, 10, "h2d_stage", 1, 95.0)
    if corr.evaluate():
        errors.append("stall selftest: conviction on a single tick")
    # Peers advanced a step before parking, so their streaks only
    # start counting now — two stale ticks convict.
    for _ in range(2):
        t[0] += 30.0
        for snap in fleet.snaps.values():
            snap.wall_ts = t[0]
            snap.beacon["age_s"] += 30.0
        verdicts = corr.evaluate()
    if (
        len(verdicts) != 1
        or verdicts[0].detector != "collective_stall"
        or verdicts[0].host != "host-c"
        or verdicts[0].node_id != 2
    ):
        errors.append(f"stall selftest: bad verdicts {verdicts}")
    inc = corr.open_incident()
    if not inc or inc["kind"] != "laggard":
        errors.append(f"stall selftest: bad incident {inc}")
    # Coordinated capture: DIAGNOSE+PROFILE to every node, once.
    if sorted({n for n, _, _ in pushes}) != [0, 1, 2]:
        errors.append(f"stall selftest: capture missed hosts {pushes}")
    if len(pushes) != 6:
        errors.append(f"stall selftest: capture count {len(pushes)}")
    # The incident trace: one root, a progress span per host.
    tl = traces.get(inc["trace_id"]) if inc else None
    names = [s["name"] for s in (tl or {}).get("spans", ())]
    if names.count("stall.incident") != 1:
        errors.append(f"stall selftest: trace roots in {names}")
    if names.count("stall.progress") != 3:
        errors.append(f"stall selftest: progress spans in {names}")
    # rc contract via the snapshot file path: 1 open, 0 resolved.
    snap = corr.snapshot()
    rendered = render_stall(snap)
    for needle in ("incident", "OPEN", "host-c", "<- culprit",
                   "STALLED"):
        if needle not in rendered:
            errors.append(
                f"stall render missing {needle!r}: {rendered!r}"
            )
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as f:
        json.dump(snap, f)
        path = f.name
    try:
        if stall_report(path) != 1:
            errors.append("stall_report rc != 1 with open incident")
        # host-c recovers: incident resolves, rc drops to 0.
        t[0] += 30.0
        fleet.set("host-a", 0, 12, "dispatch", -1, 1.0)
        fleet.set("host-b", 1, 12, "dispatch", -1, 1.0)
        fleet.set("host-c", 2, 12, "dispatch", -1, 1.0)
        if corr.evaluate():
            errors.append("stall selftest: verdict after recovery")
        if corr.open_incident() is not None:
            errors.append("stall selftest: incident not resolved")
        snap = corr.snapshot()
        if not snap["incidents"]:
            errors.append("stall selftest: resolved incident lost")
        with open(path, "w") as f:
            json.dump(snap, f)
        if stall_report(path) != 0:
            errors.append("stall_report rc != 0 after resolution")
    finally:
        os.unlink(path)
    # A flapping beacon (stale but advancing) must never convict.
    flap = StallCorrelator(
        fleet=fleet, clock=lambda: t[0],
        config={"stall_after_s": 60.0, "stall_ticks": 2.0},
    )
    step = 12
    for _ in range(5):
        t[0] += 120.0
        step += 1
        for i, h in enumerate(("host-a", "host-b", "host-c")):
            fleet.set(h, i, step, "dispatch", -1, 200.0)
        if flap.evaluate():
            errors.append("stall selftest: flapping beacon convicted")
            break
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser("obs_report")
    p.add_argument("event_file", nargs="?", default="")
    p.add_argument(
        "--failure-ts", type=float, default=None,
        help="failure instant (unix time); derived from master-side "
        "node.fail/node.gone events when omitted",
    )
    p.add_argument("--top", type=int, default=15)
    p.add_argument(
        "--goodput", action="store_true",
        help="print the goodput/badput wall-time attribution",
    )
    p.add_argument(
        "--perf", action="store_true",
        help="print the step-phase / compile / MFU summary from the "
        "profiler's trace events",
    )
    p.add_argument(
        "--health", type=str, default="",
        metavar="TARGET",
        help="render the master's fleet-health verdicts from a live "
        "master (host:port) or a HealthMonitor.snapshot() JSON file; "
        "exits 1 when a critical verdict is active",
    )
    p.add_argument(
        "--serving", type=str, default="",
        metavar="TARGET",
        help="render the master's serving plane (request counters, "
        "per-replica TTFT/TPOT/queue/KV stats, unhealthy replicas) "
        "from a live master (host:port) or a ServingRouter.snapshot()"
        " JSON file; exits 1 when a replica is unhealthy",
    )
    p.add_argument(
        "--pool", type=str, default="",
        metavar="TARGET",
        help="render the multi-job pool plane (queue depth per "
        "priority band, per-tenant quota usage, slice utilization, "
        "preemption counts, wait-time percentiles) from a live pool "
        "master (host:port) or a PoolScheduler.snapshot() JSON file",
    )
    p.add_argument(
        "--capacity", type=str, default="",
        metavar="TARGET",
        help="render the pool capacity plane (per-tenant chip-second "
        "accounting, goodput-per-chip, preemption/restore overhead, "
        "SLO error budgets with burn-rate alerts) from a live pool "
        "master (host:port) or a CapacityLedger.snapshot() JSON "
        "file; exits 1 while any tenant's error budget is burning",
    )
    p.add_argument(
        "--stall", type=str, default="",
        metavar="TARGET",
        help="render the stall-localization plane (per-host progress "
        "beacons, open/recent collective_stall incidents with the "
        "localized culprit, trace id, and coordinated-capture bundle "
        "paths) from a live master (host:port) or a "
        "StallCorrelator.snapshot() JSON file; exits 1 while an "
        "incident is open",
    )
    p.add_argument(
        "--trace", type=str, default="",
        metavar="KEY",
        help="render the causal trace timeline(s) for KEY — a trace "
        "id, a serving request id, or a node (node:<id> or bare id) "
        "— from the target given as the positional argument: a live "
        "master (host:port, TraceQueryRequest RPC) or a JSON file "
        "of trace-store timelines",
    )
    p.add_argument(
        "--postmortem", type=str, default="",
        metavar="DIR",
        help="render a forensics dir (flight-recorder bundles + "
        "faulthandler stack dumps + traces) into the last-N-seconds-"
        "before-failure report",
    )
    p.add_argument(
        "--window", type=float, default=60.0,
        help="with --postmortem: seconds before the failure instant "
        "to report on",
    )
    p.add_argument(
        "--selftest", action="store_true",
        help="run the reconstruction/goodput/fleet/postmortem "
        "pipelines on synthetic inputs",
    )
    args = p.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.health:
        return health_report(args.health)
    if args.serving:
        return serving_report(args.serving)
    if args.pool:
        return pool_report(args.pool)
    if args.capacity:
        return capacity_report(args.capacity)
    if args.stall:
        return stall_report(args.stall)
    if args.trace:
        if not args.event_file:
            p.error(
                "--trace needs a target: obs_report --trace KEY "
                "HOST:PORT|traces.json"
            )
        return trace_report(args.trace, args.event_file)
    if args.postmortem:
        from dlrover_tpu.obs.postmortem import render_postmortem

        rendered = render_postmortem(
            args.postmortem, window=args.window
        )
        print(rendered)
        return 1 if rendered.startswith("no forensics artifacts") else 0
    if not args.event_file:
        p.error("event_file is required (or pass --selftest/--postmortem)")
    return report(
        args.event_file, args.failure_ts, args.top,
        goodput=args.goodput, perf=args.perf,
    )


if __name__ == "__main__":
    sys.exit(main())
