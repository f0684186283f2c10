"""Reader ``scope_time``: device time a step by the program's own
scopes: the reduced device trace (``trace_reduce.reduce``: self
seconds by instruction name) joined to the program's description of
its compiled step (``dlrover_tpu.obs.profiling.compiled_scopes``:
instruction name -> the ``jax.named_scope`` path it was traced under,
and the pass).

args: {"scope": <name>, "nested": <false>}

**The rule.** An instruction belongs to the **first** of ``embed``,
``attn``, ``mlp``, ``ssm``, ``head``, ``optimizer`` on its path,
outermost first; with none of them, to the innermost of ``layers``
(a model's scan over its layers) and ``accumulate`` (the trainer's
scan over the microbatches, and with it what the loss computes under
no scope of its own, as the last norm); with neither it is
``unscoped``: what the compiler wrote no ``op_name`` for, or an
instruction the description does not have. So the top-level numbers
**partition** the device's busy time: over the nine names they sum
to ``device.busy_s / steps``. A fusion of operations from two scopes
carries its root's name and goes where its root goes. ``layers`` and
``accumulate`` read what the scans cost themselves: the slices of the
stacked parameters, the stacking of what the backward keeps, the
``while``, the accumulator's zeros and scaled add.

``nested`` true reads an inner scope wherever it stands on the path
(``moe_route`` inside ``mlp``, ``ssm_conv`` inside ``ssm``): a part of
a top-level number, not a term of the partition.

The program is asked once a run, after the window (0.04-0.14 s on a
v5e: JAX still holds the step's lowering and executable; at worst a
lowering and a cache-served compile) and the answer kept in
``ctx``. Nothing to read is ``None`` and the metric is left out: no
device plane (a rehearsal on the CPU), a trainer in another process
(the resume cell), a program older than the description, or a
description that knows under 99% of the window's device time, which
is then not of the program that ran (``notes.scope_matched_share``
says so).

``ctx["notes"]`` (the line's ``notes``) gets ``scope_split``: each
top-level name's ``fwd`` / ``bwd`` / ``recompute`` ms a step (the
recompute is what remat computes again, inside the backward);
``scope_of_top_ops``: scope path and pass of the ten instructions
``breakdown.device_ops`` lists; ``scope_longest_ops``: the three
longest instructions of each top-level name with their ms a step
(what ``layers`` or ``unscoped`` is made of); ``scope_inner_ms``: ms
a step of every scope inside a top-level one (``moe_route``,
``ssm_conv``, ``ssd``, ``ssm_norm``), metric or not;
``scope_matched_share``; ``scope_description_s``, the seconds the
program took to answer.
"""

from __future__ import annotations

import sys
import time

FN_NAME = "train_step"
LAYER_SCOPES = ("embed", "attn", "mlp", "ssm", "head", "optimizer")
SCAN_SCOPES = ("layers", "accumulate")
OUTER_SCOPES = frozenset(LAYER_SCOPES + SCAN_SCOPES + ("",))
UNSCOPED = "unscoped"
MATCHED_AT_LEAST = 0.99
LONGEST = 3


def top_level(path: str) -> str:
    """The one name of the partition a scope path belongs to."""
    parts = path.split("/") if path else []
    for part in parts:
        if part in LAYER_SCOPES:
            return part
    for part in reversed(parts):
        if part in SCAN_SCOPES:
            return part
    return UNSCOPED


def describe():
    """The program's description of its step, or None (the parent of
    the PR that brought it has no such function)."""
    from dlrover_tpu.obs import profiling

    ask = getattr(profiling, "compiled_scopes", None)
    if ask is None:
        return None
    try:
        return ask(FN_NAME)
    except Exception as exc:  # noqa: BLE001 - a reading, not the run
        print(f"[bench] no description of {FN_NAME}: {exc!r}", file=sys.stderr)
        return None


def join(reduced: dict, description: dict) -> dict:
    """The window's operations by scope, ms a step: {"split": top-level
    name -> pass -> ms, "inner": inner scope -> ms, "longest": top-level
    name -> its longest instructions, "top_ops", "matched_share"}."""
    matched, total = 0.0, 0.0
    per_step_ms = 1e3 / reduced["steps"]
    split, longest, inner = {}, {}, {}
    for name, rec in reduced["ops"].items():
        entry = description.get(name)
        total += rec["seconds"]
        if entry is not None:
            matched += rec["seconds"]
        entry = entry or {"scope": "", "pass": "fwd"}
        top, ms = top_level(entry["scope"]), rec["seconds"] * per_step_ms
        by_pass = split.setdefault(top, {})
        by_pass[entry["pass"]] = by_pass.get(entry["pass"], 0.0) + ms
        longest.setdefault(top, []).append([name, ms])
        # (moe.py enters "moe_route" inside "moe_route": once a name.)
        for part in set(entry["scope"].split("/")) - OUTER_SCOPES:
            inner[part] = inner.get(part, 0.0) + ms
    return {
        "matched_share": matched / total if total else 0.0,
        "split": split,
        "inner": inner,
        "longest": {
            top: sorted(ops, key=lambda op: -op[1])[:LONGEST]
            for top, ops in longest.items()
        },
        "top_ops": {
            name: "{scope} {pass}".format(**description[name]).strip()
            if name in description else "not in the description"
            for name, _ in reduced["device_ops"]
        },
    }


def _table(ctx: dict):
    """The joined table, made once a run and kept in ``ctx``."""
    if "scope_time" not in ctx:
        ctx["scope_time"] = None
        t0 = time.perf_counter()
        description = describe()
        if description:
            table = join(ctx["trace"], description)
            notes = ctx.setdefault("notes", {})
            notes["scope_description_s"] = time.perf_counter() - t0
            notes["scope_matched_share"] = table["matched_share"]
            if table["matched_share"] >= MATCHED_AT_LEAST:
                notes["scope_split"] = table["split"]
                notes["scope_of_top_ops"] = table["top_ops"]
                notes["scope_longest_ops"] = table["longest"]
                notes["scope_inner_ms"] = table["inner"]
                ctx["scope_time"] = table
    return ctx["scope_time"]


def read(ctx: dict, scope: str, nested: bool = False):
    red = ctx.get("trace") or {}
    if not red.get("steps") or not red.get("ops"):
        return None
    table = _table(ctx)
    if table is None:
        return None
    if nested:
        return table["inner"].get(scope)
    by_pass = table["split"].get(scope)
    return sum(by_pass.values()) if by_pass else None  # no such scope: None
