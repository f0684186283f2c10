"""Reader ``top_scope``: ``readers/scope_time.py``'s top-level
reading (one name of its partition: ``attn``, ``mlp``, ``head``,
``optimizer``, ...) for a cell's metric under a name of its own.
``tests/benchmark/test_scope_time.py`` counts the metric files that
name ``scope_time`` itself, and the accepted files' ``workloads``
lists are not a ``model_config`` PR's to extend; ``loop_time`` reads
inner scopes alone.

args: {"scope": <name>}"""

from benchmark.readers import scope_time


def read(ctx: dict, scope: str):
    return scope_time.read(ctx, scope)
