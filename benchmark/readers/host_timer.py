"""Reader ``host_timer``: a statistic of a host timing the trainer
script took around a call into the program, one sample a step.

args: {"field": "data_wait_ms" | "dispatch_ms"}: a key of
``step_metrics.window_metrics``."""


def read(ctx: dict, field: str):
    return (ctx.get("window") or {}).get(field)
