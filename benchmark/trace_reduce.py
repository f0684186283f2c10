"""From the profiler's trace to numbers: busy union, idle share, gaps
by what the host was doing, device time by operation, collective time
that nothing hid.

Two stages, so that the second can be checked on a small recorded
trace (``testdata/``) without a chip:

1. ``load_events``: an ``.xplane.pb`` file (``jax.profiler.ProfileData``)
   or this module's own ``.events.json.gz`` dump -> flat events
   ``{"plane", "line", "name", "start", "dur", "category"}`` in
   nanoseconds. Device planes are ``/device:TPU:<n>``; the host's
   annotations are on ``/host:CPU``.
2. ``reduce``: events -> the metrics, over a steady window: from the
   start of the second execution of the step program inside the trace
   to the end of the last, so that neither the profiler's start-up nor
   the drained queue before it counts as idle time.

On a device's ``XLA Ops`` line a ``while`` (the layer scan) or a
fusion's call encloses the operations of its body; ``self_times``
takes each event's children off it, so that time is counted once.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute",
    re.I,
)
HOST_SPAN_PREFIX = "bench."
EVENT_KEYS = ("plane", "line", "name", "start", "dur", "category")
# A device event is named by its whole HLO instruction,
# "%flash_attention_fwd.17 = (bf16[...]) custom-call(...), custom_call_target=...":
# the name is what stands before " = ", the category its opcode (the
# first lower-case word that opens a parenthesis; shapes and layouts
# open theirs after a bracket or a capital) and a custom call's target.
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def split_hlo_name(name: str) -> tuple:
    """("flash_attention_fwd.17", "custom-call:tpu_custom_call")."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name.lstrip("%"), ""
    op = _OPCODE.search(" " + rest)
    category = op.group(1) if op else ""
    target = _TARGET.search(rest)
    if target:
        category += ":" + target.group(1)
    return head.lstrip("%"), category


def find_xplane(trace_dir: str):
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return paths[-1] if paths else None


def load_events(path: str) -> list:
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            rows = json.load(f)["events"]
        return [dict(zip(EVENT_KEYS, r)) for r in rows]
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                name, category = ev.name, ""
                if device and line.name == OPS_LINE:
                    name, category = split_hlo_name(ev.name)
                out.append({
                    "plane": plane.name, "line": line.name, "name": name,
                    "start": float(ev.start_ns), "dur": float(ev.duration_ns),
                    "category": category,
                })
    return out


def dump_events(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"events": [[e[k] for k in EVENT_KEYS] for e in events]}, f)


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: list, lo: float, hi: float) -> list:
    return [
        [max(s, lo), min(e, hi)] for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def subtract(a: list, b: list) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events: list) -> list:
    """(event, self nanoseconds) for the events of ONE line: an
    event's duration less the durations of the events nested directly
    inside it."""
    order = sorted(events, key=lambda e: (e["start"], -e["dur"]))
    selfs = [e["dur"] for e in order]
    stack = []  # indices into order
    for i, e in enumerate(order):
        while stack and (
            order[stack[-1]]["start"] + order[stack[-1]]["dur"] <= e["start"]
        ):
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e["dur"]
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, selfs)]


def steady_window(events: list, devices: list) -> tuple:
    """[start of the second step program's execution, end of the
    last] on the first device; with fewer than three executions, or no
    ``XLA Modules`` line, the span of all device operations."""
    mods = sorted(
        (e for e in events
         if e["plane"] == devices[0] and e["line"] == MODULES_LINE),
        key=lambda e: e["start"],
    )
    # The step program is the module that takes most of the time.
    by_name = {}
    for e in mods:
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"]
    if by_name:
        top = max(by_name, key=by_name.get)
        steps = [e for e in mods if e["name"] == top]
        if len(steps) >= 3:
            return (
                steps[1]["start"],
                steps[-1]["start"] + steps[-1]["dur"],
                len(steps) - 1,
                top,
            )
    ops = [e for e in events if e["plane"] in devices and e["line"] == OPS_LINE]
    if not ops:
        return None
    return (
        min(e["start"] for e in ops),
        max(e["start"] + e["dur"] for e in ops),
        0,
        "",
    )


def reduce(events: list, top: int = 10) -> dict:
    """The trace's numbers; ``{}`` when no device operation is in it."""
    devices = sorted(
        {e["plane"] for e in events if DEVICE_PLANE.match(e["plane"])},
        key=lambda p: int(p.rsplit(":", 1)[1]),
    )
    if not devices:
        return {}
    win = steady_window(events, devices)
    if win is None:
        return {}
    lo, hi, n_steps, step_module = win
    host_spans = [
        e for e in events
        if not DEVICE_PLANE.match(e["plane"])
        and e["name"].startswith(HOST_SPAN_PREFIX)
    ]
    busy_s, coll_s, exposed_s = [], [], []
    by_name = {}
    gaps_by_span = {}
    for dev in devices:
        ops = [
            e for e in events
            if e["plane"] == dev and e["line"] == OPS_LINE
            and e["start"] + e["dur"] > lo and e["start"] < hi
        ]
        busy = clip(union([[e["start"], e["start"] + e["dur"]] for e in ops]),
                    lo, hi)
        busy_s.append(total(busy) / 1e9)
        spans, in_flight, exposed_ns = [], {}, 0.0
        for e, self_ns in self_times(ops):
            # Leaves carry the time; an enclosing while or call keeps
            # what is left of it once its body is taken off.
            rec = by_name.setdefault(
                e["name"],
                {"seconds": 0.0, "count": 0, "category": e["category"],
                 "total_seconds": 0.0},
            )
            rec["seconds"] += self_ns / 1e9 / len(devices)
            rec["total_seconds"] += e["dur"] / 1e9 / len(devices)
            rec["count"] += 1.0 / len(devices)
            kind = COLLECTIVE.search(e["name"])
            if not kind:
                continue
            # One operation at a time runs on this line, so while a
            # collective's own event lasts nothing else computes: that
            # is the exposed part. An asynchronous one is a short
            # "-start" and a "-done" that waits for what is left; the
            # transfer is in flight from the one to the other.
            exposed_ns += self_ns
            end = e["start"] + e["dur"]
            key = kind.group(0).lower().replace("_", "-")
            if "-start" in e["name"]:
                in_flight.setdefault(key, []).append(e["start"])
            elif "-done" in e["name"] and in_flight.get(key):
                spans.append([in_flight[key].pop(0), end])
            else:
                spans.append([e["start"], end])
        coll_s.append(total(clip(union(spans), lo, hi)) / 1e9)
        exposed_s.append(exposed_ns / 1e9)
        if dev == devices[0]:
            for s, e in subtract([[lo, hi]], busy):
                best, best_overlap = "unannotated", 0.0
                for h in host_spans:
                    ov = min(e, h["start"] + h["dur"]) - max(s, h["start"])
                    if ov > best_overlap:
                        best, best_overlap = h["name"], ov
                gaps_by_span[best] = gaps_by_span.get(best, 0.0) + (e - s) / 1e9
    n = len(devices)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1]["seconds"])
    return {
        "n_devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "steps": n_steps,
        "step_module": step_module,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "ops": {k: v for k, v in ranked},
        "device_ops": [[k, v["seconds"]] for k, v in ranked[:top]],
        "idle_gaps": sorted(
            ([k, v] for k, v in gaps_by_span.items()), key=lambda kv: -kv[1]
        )[:top],
    }


def matching_ops(reduced: dict, name_pattern: str = "", category_pattern: str = ""):
    """[(name, record)] of the reduced trace's operations whose name or
    category matches."""
    out = []
    for name, rec in reduced.get("ops", {}).items():
        if name_pattern and not re.search(name_pattern, name):
            continue
        if category_pattern and not re.search(
            category_pattern, rec.get("category", "")
        ):
            continue
        out.append((name, rec))
    return out
