"""From step records to the end-to-end numbers. No JAX.

A record is what the trainer script wrote when it saw a step finish:
``{"step", "t_done", "loss", "data_wait_s", "dispatch_s"[, "save"]}``,
``t_done`` on the host's ``time.time()``. The window runs from its
``window_open`` record to the last step completion in it.
"""

from __future__ import annotations

import json
import math
import statistics


def read_records(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    break  # the line a killed process was writing
    return out


def split_incarnations(records: list) -> list:
    """Records of each process in turn: a ``window_open`` starts one."""
    out = []
    for r in records:
        if "window_open" in r:
            out.append({"open": r["window_open"], "pid": r.get("pid"), "steps": []})
        elif "t_done" in r and out:
            out[-1]["steps"].append(r)
    return out


def p90(values: list) -> float:
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def window_metrics(t_open: float, steps: list, tokens_per_step: int,
                   steps_per_sample: int = 1, after: float = None) -> dict:
    """``after``: count only steps that finished after that time (a
    traced run leaves its traced part out of the rate)."""
    if after is not None:
        kept = [s for s in steps if s["t_done"] > after]
        if len(kept) >= 3:
            t_open, steps = kept[0]["t_done"], kept[1:]
    if len(steps) < 2:
        return {}
    t_close = steps[-1]["t_done"]
    done = [s["t_done"] for s in steps]
    intervals = [b - a for a, b in zip(done, done[1:])]
    # A step carried a save if one was taken in it; a save the program
    # dropped (``save_ok`` false) makes its step neither: it is counted.
    tried = [s.get("save") for s in steps[1:]]
    carried_save = [sv is not None and sv.get("save_ok", True) for sv in tried]
    plain = [d for d, sv in zip(intervals, tried) if sv is None]
    g = max(int(steps_per_sample), 1)
    grouped = [
        (done[k + g] - done[k]) / g for k in range(0, len(done) - g, g)
    ]
    out = {
        "window_s": t_close - t_open,
        "steps": len(steps),
        "tokens_per_s": len(steps) * tokens_per_step / (t_close - t_open),
        "step_ms_median": statistics.median(plain) * 1e3 if plain else None,
        "step_samples": len(grouped),
        "step_ms_p90": p90(grouped) * 1e3 if len(grouped) >= 10 else None,
        "data_wait_ms": statistics.median(s["data_wait_s"] for s in steps) * 1e3,
        "dispatch_ms": statistics.median(s["dispatch_s"] for s in steps) * 1e3,
    }
    stalls = [
        d - statistics.median(plain)
        for d, sv in zip(intervals, carried_save) if sv and plain
    ]
    if stalls:
        out["saves"] = len(stalls)
        out["save_stall_ms"] = statistics.median(stalls) * 1e3
        calls = [sv["save_s"] for sv, ok in zip(tried, carried_save)
                 if ok and "save_s" in sv]
        if calls:
            out["save_call_ms"] = statistics.median(calls) * 1e3
    # For whoever looks for a stall: the three longest intervals and
    # what the host spent in them.
    out["longest_steps"] = [
        {"step": s["step"], "ms": d * 1e3,
         **{k[:-1] + "ms": s[k] * 1e3 for k in (
             "data_wait_s", "dispatch_s", "read_loss_s", "log_s") if k in s},
         **({"save_s": s["save"].get("save_s")} if s.get("save") else {})}
        for d, s in sorted(
            zip(intervals, steps[1:]), key=lambda p: -p[0]
        )[:3]
    ]
    if any(sv is not None for sv in tried):
        out["saves_dropped"] = sum(
            sv is not None and not sv.get("save_ok", True) for sv in tried
        )
    return out


def losses_ok(steps: list) -> dict:
    """(b) of ``correct``: every loss finite, and the mean of the last
    ten below the mean of the first ten."""
    losses = [s["loss"] for s in steps]
    finite = [math.isfinite(x) for x in losses]
    k = min(10, len(losses) // 2)
    falls = k > 0 and (sum(losses[-k:]) / k) < (sum(losses[:k]) / k)
    return {
        "non_finite": len(losses) - sum(finite),
        "falls": bool(falls),
        "first": losses[:k],
        "last": losses[-k:],
    }
