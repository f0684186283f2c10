"""Reader ``startup``: the parts of a start, from the program's own
start-up timeline (``dlrover_tpu.obs.profiling.startup_timeline``:
the phase marks a process placed, and JAX's own account of every
trace, lowering, backend compile and persistent-cache load as
``{stage, fn, t0, t1}`` records), all on ``time.time()``.

args, one of:

  {"from": <mark>, "to": <mark>}
      seconds between two marks;
  {"stage": <stage or list>, "until": <mark>, "fn": <regex, optional>,
   "what": "seconds" | "count"}
      the records of those stages that ended by ``until`` (so that
      the lowering ``compiled_scopes`` asks for after the window is
      not counted), of the functions whose name the regex finds, as
      the seconds they cover together (a jitted function traced
      inside another's trace is counted once) or as their number.

**The first launch.** A writer that starts a new set of marks (the
trainer's ``proc_start``, the agent's ``agent.exit_seen``) keeps the
last one under ``prev.``. So a mark resolves to ``prev.<mark>`` once
its writer has ``prev.`` keys, and to ``<mark>`` until then: after
the resume cell's one restart these are the marks of the first
trainer and of the launcher before it, which is what the cell's
``setup_s`` times, and a mark that only the relaunch placed reads
``None``.

**Where the marks come from.** ``ctx["marks"]`` where that is not
empty (the resume cell: the phases file both processes wrote), else
``startup_timeline()["marks"]`` in this process (the steady cells,
as ``readers/scope_time.py`` asks the program for its compiled
scopes). The stage records are this process's only: with marks from
a file the trainer was another process, and a stage metric reads
``None``. A mark that is missing, or a program older than the
timeline (this PR's parent), reads ``None``, never 0.

Every reading fills ``ctx["notes"]["setup_marks"]`` (the line's
``notes``): each mark of the first launch in seconds after its
``proc_start``, the marks no metric reads (``accelerate_done``,
``restore_done``) among them.

``what: "count"`` also fills ``ctx["notes"]["setup_compile_by_fn"]``:
the counted records by function, ``{fn: {"n", "seconds", <stage>:
n}}``, the ``LISTED`` longest by name and the rest under
``"(others)"``: whether a start lowers, compiles or loads its step
more than once.
"""

from __future__ import annotations

import re

PREV = "prev."
AGENT = "agent."
LISTED = 12
OTHERS = "(others)"


def first_launch(marks: dict, name: str):
    """The mark of the first launch, or None: under ``prev.`` once
    its writer (the agent for ``agent.*``, else the trainer) has
    started a new set, under its plain name until then. A mark only
    the relaunch placed is not the first launch's. (Whose key a
    ``prev.`` key is, is written out here and not asked of the
    program: the parent's phases file is read by this too.)"""
    agents = name.startswith(AGENT)
    moved = any(
        k.startswith(PREV) and k[len(PREV):].startswith(AGENT) == agents
        for k in marks
    )
    return marks.get(PREV + name if moved else name)


def offsets(marks: dict) -> dict:
    """Every mark of the first launch, in seconds after its
    ``proc_start`` (the launcher's stand before it: negative)."""
    start = first_launch(marks, "proc_start")
    if start is None:
        return {}
    found = {
        name: first_launch(marks, name)
        for name in sorted({k.removeprefix(PREV) for k in marks})
    }
    return {k: t - start for k, t in found.items() if t is not None}


def _program():
    """The module that keeps the program's own timeline
    (``startup_timeline()``, ``union_seconds``), or None in a program
    older than it."""
    from dlrover_tpu.obs import profiling

    return profiling if hasattr(profiling, "startup_timeline") else None


def by_function(records: list) -> dict:
    table = {}
    for r in records:
        row = table.setdefault(r["fn"], {"n": 0, "seconds": 0.0})
        row["n"] += 1
        row["seconds"] += r["t1"] - r["t0"]
        row[r["stage"]] = row.get(r["stage"], 0) + 1
    ranked = sorted(table, key=lambda fn: -table[fn]["seconds"])
    out = {fn: table[fn] for fn in ranked[:LISTED]}
    if ranked[LISTED:]:
        rest = out[OTHERS] = {"n": 0, "seconds": 0.0}
        for fn in ranked[LISTED:]:
            for key, v in table[fn].items():
                rest[key] = rest.get(key, 0) + v
    return out


def read(ctx: dict, **args):
    in_file = ctx.get("marks") or None
    program = None if in_file else _program()
    timeline = program.startup_timeline() if program else None
    marks = in_file or (timeline or {}).get("marks")
    if marks is None:
        return None
    notes = ctx.setdefault("notes", {})
    if "setup_marks" not in notes:
        notes["setup_marks"] = offsets(marks)
    if "from" in args:
        a, b = (first_launch(marks, args[k]) for k in ("from", "to"))
        return None if a is None or b is None else b - a
    until = first_launch(marks, args["until"])
    if until is None or timeline is None:
        return None
    stages = args["stage"]
    stages = (stages,) if isinstance(stages, str) else tuple(stages)
    fn = re.compile(args.get("fn", ""))
    kept = [
        r for r in timeline["compile"]
        if r["stage"] in stages and r["t1"] <= until and fn.search(r["fn"])
    ]
    if args.get("what", "seconds") == "count":
        notes["setup_compile_by_fn"] = by_function(kept)
        return len(kept)
    return program.union_seconds(kept)
