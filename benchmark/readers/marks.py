"""Reader ``marks``: seconds between two marks on the host's clock.

The marks are ``TrainingMonitor.mark_phase``'s of the restarted
trainer (``proc_start``, ``dist_ready``, ``built``, ``restore_done``,
``first_step_done``) and the parent's ``kill``, all ``time.time()``.

args: {"from": <mark>, "to": <mark>}."""


def read(ctx: dict, **args):
    marks = ctx.get("marks") or {}
    a, b = marks.get(args["from"]), marks.get(args["to"])
    if a is None or b is None:
        return None
    return b - a
