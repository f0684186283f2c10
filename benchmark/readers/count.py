"""Reader ``count``: a number the run counted.

args: {"key": <name in the run's counts>, "scale": <factor, default 1>}."""


def read(ctx: dict, key: str, scale: float = 1.0):
    value = (ctx.get("counts") or {}).get(key)
    return None if value is None else value * scale
