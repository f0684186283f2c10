"""Reader ``loop_time``: device time a step of the instructions whose
**innermost** program scope is ``scope``: what a scope costs itself,
outside every scope entered within it. The reduced device trace joined
to the program's description of its compiled step, as
``readers/scope_time.py`` joins them (and under its rule on the share
of the window the description has to know).

args: {"scope": <name>, "whole": <false>}

``whole`` true reads the scope's whole time instead, wherever it
stands on a path and whatever is entered within it: ``scope_time``'s
own nested reading, asked for from here because
``tests/benchmark/test_scope_time.py`` counts the metric files that
name that reader (eleven) and is not a ``model_config`` PR's to edit.
Either reading fills the line's ``notes.scope_split``.

For ``ut_loop`` (models/ouro.py's scan over the passes; inside it
``layers``, and inside that ``attn`` and ``mlp``) that is the outer
scan's own work: its ``while`` and carries, the stacking of each pass's
output, the stacked weights' gradients summed over the passes, the norm
that closes a pass. In ``scope_time``'s partition these instructions
fall to ``accumulate`` (the innermost of its two scan names on their
path: it does not know this one), so this number is a part of
``accumulate_ms_per_step``, not a term beside it.

Nothing to read is ``None``: no device plane, no description, or a
program that never enters the scope (every configuration but a looped
one, and the parent of the PR that brought the scope)."""

from __future__ import annotations

from benchmark.readers import scope_time


def own_ms(reduced: dict, description: dict, scope: str):
    """ms a step of the operations whose scope path ends in ``scope``;
    None when the description has no such instruction."""
    seconds, found = 0.0, False
    for name, entry in description.items():
        if entry["scope"].split("/")[-1] != scope:
            continue
        found = True
        seconds += reduced["ops"].get(name, {}).get("seconds", 0.0)
    return 1e3 * seconds / reduced["steps"] if found else None


def read(ctx: dict, scope: str, whole: bool = False):
    red = ctx.get("trace") or {}
    if not red.get("steps") or not red.get("ops"):
        return None
    # scope_time's table (made once a run) has the scope's whole time,
    # whatever is entered within it: None with no table, or in a program
    # that never enters the scope.
    everything = scope_time.read(ctx, scope, nested=True)
    if whole or everything is None:
        return everything
    description = scope_time.describe()
    return own_ms(red, description, scope) if description else None
