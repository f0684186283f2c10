"""Traffic kind ``steady``: the trainer loop in this process, one
micro-batch a step, no checkpoint, for ``--seconds``."""

from __future__ import annotations

import shutil
import tempfile

from benchmark import step_metrics
from benchmark.kinds import common


def run(cell: dict, opts: dict) -> dict:
    from benchmark import trainer_loop

    trace_dir = tempfile.mkdtemp(prefix="bk_trace_") if opts["trace"] else None
    try:
        report = trainer_loop.train({
            "cell": cell,
            "seed": opts["seed"],
            "seconds": opts["seconds"],
            "trace": opts["trace"],
            "trace_dir": trace_dir,
            "allow_cpu": opts["allow_cpu"],
            "t_start": opts["t_start"],
        })
        reduced = (
            common.reduce_trace(trace_dir, opts.get("dump_events", ""))
            if trace_dir else {}
        )
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    records = report["records"]
    inc = step_metrics.split_incarnations(records)[0]
    workload = cell["workload"]
    after = (report.get("trace_window") or {}).get("t1")
    window = step_metrics.window_metrics(
        inc["open"], inc["steps"], report["tokens_per_step"],
        int(workload.get("steps_per_sample", 1)), after=after,
    )
    why = []
    failed = common.check_losses(inc["steps"], why)
    if not common.reference_ok(report["reference"]):
        why.append(f"reference check: {report['reference']}")
    if report["step_programs"] != 1:
        why.append(f"{report['step_programs']} step programs compiled")
    if report["compiles_in_window"] and not opts["trace"]:
        why.append(f"{report['compiles_in_window']} compile event(s) in the window")
    values = {
        "setup_s": report["setup_s"],
        "tokens_per_s": window.get("tokens_per_s"),
        "step_ms_p90": window.get("step_ms_p90"),
    }
    return {
        "correct": not why,
        "why": why,
        "attempted": len(inc["steps"]),
        "failed": failed,
        "values": values,
        "device": common.device_block(report["device"], [report]),
        "ctx": {
            "window": window,
            "trace": reduced,
            "counts": {
                "step_programs": report["step_programs"],
                "step_hbm_bytes": common.step_hbm_total(report),
            },
            "marks": {},
        },
        "detail": {
            "reference": report["reference"],
            "window": window,
            "first_step_cache": [report.get("first_step_cache_hits"),
                                 report.get("first_step_cache_misses")],
            "compile_cache_dir": report["compile_cache_dir"],
            "step_hbm": report.get("step_hbm"),
            "peak_bytes_in_use": report.get("peak_bytes_in_use"),
        },
    }
