"""What the traffic kinds share: the checks behind ``correct`` and the
shape of what a kind hands back to ``run.py``."""

from __future__ import annotations

import os

from benchmark import step_metrics

def reference_error(system: list, reference: list) -> dict:
    """The system's losses against the reference's on the same seeded
    sequences, one pair a sequence (or a group of one per chip):
    ``mean_rel``, the difference of the two means over the reference's
    mean, and ``rms_rel``, the root mean square of the pairs' relative
    differences, which no cancelling between sequences can shrink."""
    n = len(reference)
    mean_ref = sum(reference) / n
    return {
        "mean_rel": abs(sum(system) / n - mean_ref) / abs(mean_ref),
        "rms_rel": (
            sum(((s - r) / r) ** 2 for s, r in zip(system, reference)) / n
        ) ** 0.5,
    }


# The system computes in bf16 with f32 accumulation, the reference in
# f32 at "highest" precision, on the same (bf16-valued) weights. What
# ``rms_rel`` reads on the chip (my chip runs, PR 23,
# ``calibrate_reference.py``, three seeds, GPT-2 124M on 8 sequences
# and Mistral at 2 layers on 4; and the mean of every earlier run):
#   the system as it is        1.1e-5 .. 5.2e-5; 1.6e-4 the largest of
#                              60 readings (Mistral, 8 layers, fsdp=4)
#   weights rounded to e4m3    5.4e-4 .. 4.9e-3
#   weights rounded to e5m2    6.2e-4 .. 1.5e-3
#   Mistral's window off       8.5e-4 .. 2.1e-3
# The system's own difference is a bias of the seed's weights (the
# same sign on every sequence), so more sequences do not shrink it;
# the tolerance sits between the two groups, 1.9 times over the one
# and 1.8 times under the other. It is one number for every
# configuration: a later configuration cannot bring a looser one.
REFERENCE_REL_TOL = 3e-4


def reference_ok(reference: dict) -> bool:
    return bool(reference) and reference["rms_rel"] <= REFERENCE_REL_TOL


def device_block(device: dict, reports: list) -> dict:
    """``memory_peak_bytes``: the larger of the allocator's peak on the
    fullest chip and the compiled step's arguments plus temporaries
    (``memory_analysis``); the allocator's counter leaves the
    temporaries out on this backend."""
    peak = 0
    for rep in reports:
        hbm = rep.get("step_hbm") or {}
        peak = max(
            peak,
            int(rep.get("peak_bytes_in_use") or 0),
            int(hbm.get("argument_bytes", 0)) + int(hbm.get("temp_bytes", 0)),
        )
    return {**device, "memory_peak_bytes": peak}


def step_hbm_total(report: dict):
    hbm = report.get("step_hbm") or {}
    if not hbm:
        return None
    return hbm["argument_bytes"] + hbm["temp_bytes"]


def check_losses(steps: list, why: list) -> int:
    """Appends what is wrong to ``why``; returns the failed steps."""
    res = step_metrics.losses_ok(steps)
    if res["non_finite"]:
        why.append(f"{res['non_finite']} loss(es) not finite")
    if not res["falls"]:
        why.append(
            f"the loss did not fall: first {res['first']}, last {res['last']}"
        )
    return res["non_finite"]


def reduce_trace(trace_dir: str, dump_to: str = "") -> dict:
    """The reduced trace under ``trace_dir`` (``{}`` if the profiler
    left none); ``dump_to`` also keeps its events for a look by hand."""
    from benchmark import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    if not path:
        return {}
    events = trace_reduce.load_events(path)
    if dump_to:
        os.makedirs(os.path.dirname(dump_to) or ".", exist_ok=True)
        trace_reduce.dump_events(events, dump_to)
    return trace_reduce.reduce(events)
