"""Reader ``trace_events``: a number from the reduced device trace
(``trace_reduce.reduce``).

args["what"]:
  "per_step_ms":   device time a step of the operations that match
                   ``name`` / ``category`` (regular expressions).
  "roofline":      for the kernel whose events match ``name``: the
                   least time the chip could take for those calls
                   (``kernel_work/<args["kernel"]>.py``'s count against
                   ``peaks.json``), over the time they took, percent.
  "collective_exposed_ms": a step's collective time during which no
                   other operation ran on that device.
"""

from benchmark import flops, trace_reduce


def read(ctx: dict, what: str, name: str = "", category: str = "",
         kernel: str = ""):
    red = ctx.get("trace") or {}
    steps = red.get("steps") or 0
    if not red or not steps:
        return None
    if what == "collective_exposed_ms":
        if red.get("collective_s", 0.0) <= 0.0:
            return None
        return red["collective_exposed_s"] / steps * 1e3
    ops = trace_reduce.matching_ops(red, name, category)
    if not ops:
        return None
    seconds = sum(rec["total_seconds"] for _, rec in ops)
    calls = sum(rec["count"] for _, rec in ops)
    if seconds <= 0:
        return None
    if what == "per_step_ms":
        return seconds / steps * 1e3
    if what == "roofline":
        if not ctx.get("peaks"):
            return None  # a rehearsal off the chip: no peak to hold it to
        work = flops.kernel_work(
            kernel, ctx["cell"]["config"],
            int(ctx["cell"]["workload"]["micro_batch_per_chip"]),
        )
        least = flops.roofline_seconds(work, ctx["peaks"])
        ctx.setdefault("notes", {})[f"{kernel}_bound"] = least["bound"]
        return 100.0 * least["seconds"] * calls / seconds
    raise ValueError(f"trace_events knows no {what!r}")
