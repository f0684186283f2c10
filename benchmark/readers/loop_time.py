"""Reader ``loop_time``: device time a step of the instructions whose
**innermost** program scope is ``scope``: what a scope costs itself,
outside every scope entered within it. The reduced device trace joined
to the program's description of its compiled step, as
``readers/scope_time.py`` joins them (and under its rule on the share
of the window the description has to know).

args: {"scope": <name>}

A scope's whole time, wherever it stands on a path and whatever is
entered within it, is ``scope_time``'s ``nested`` reading. This
reading fills the line's ``notes.scope_split`` as that one does.

For ``ut_loop`` (models/ouro.py: since PR 45 ``ut_steps`` calls in a
row of one jitted pass, the layers' scan and the norm that closes it;
inside the scope ``layers``, and inside that ``attn`` and ``mlp``) the
scope's own work is the zero fills of the kept stacks before each layer
scan writes them and of the backward scans' gradient stacks, the norm
that closes a pass and the stacking of the passes' outputs; the
weights' gradients summed over the passes are not here (XLA fuses the
sums into the optimizer's reads). In ``scope_time``'s partition these
instructions fall to ``accumulate`` (the innermost of its two scan
names on their path: it does not know this one), so this number is a
part of ``accumulate_ms_per_step``, not a term beside it.

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


def read(ctx: dict, scope: str):
    red = ctx.get("trace") or {}
    if not red.get("steps") or not red.get("ops"):
        return None
    # scope_time's table (made once a run) has the scope's whole time:
    # None with no table, or in a program that never enters the scope.
    if scope_time.read(ctx, scope, nested=True) is None:
        return None
    description = scope_time.describe()
    return own_ms(red, description, scope) if description else None
