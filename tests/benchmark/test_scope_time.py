"""Reader ``scope_time`` on a trace recorded on the chip with the
program's own description of the step that ran
(``benchmark/testdata/gpt2_scopes_v5e.*``: ``gpt2-124m.steady``,
``--trace 1 --dump-events``, three executions of the step), and on
small made-up traces for the edges: no trace, no description, a
description of another program.
"""

import gzip
import json
import os

import pytest

from benchmark import cell as cell_files
from benchmark import trace_reduce
from benchmark.readers import scope_time

TESTDATA = os.path.join(cell_files.HERE, "testdata")
BASE = os.path.join(TESTDATA, "gpt2_scopes_v5e")
TOP_LEVEL = ("embed", "attn", "mlp", "ssm", "head", "optimizer", "layers",
             "accumulate", "unscoped")


@pytest.fixture(scope="module")
def recorded():
    reduced = trace_reduce.reduce(
        trace_reduce.load_events(BASE + ".events.json.gz")
    )
    with gzip.open(BASE + ".description.json.gz", "rt") as f:
        description = json.load(f)
    with open(BASE + ".expected.json") as f:
        expected = json.load(f)
    return reduced, description, expected


@pytest.fixture
def ctx(recorded, monkeypatch):
    reduced, description, _ = recorded
    asked = []

    def describe():
        asked.append(1)
        return description

    monkeypatch.setattr(scope_time, "describe", describe)
    return {"trace": reduced, "asked": asked}


@pytest.mark.parametrize("scope", [
    "embed", "attn", "mlp", "head", "optimizer", "layers", "accumulate",
    "unscoped",
])
def test_recorded_trace_reads_the_expected_ms(ctx, recorded, scope):
    want = recorded[2]["ms_per_step"][scope]
    assert scope_time.read(ctx, scope) == pytest.approx(want, rel=1e-9)
    assert want > 0


def test_top_level_values_partition_the_busy_time(ctx, recorded):
    reduced, _, expected = recorded
    assert reduced["steps"] == expected["steps"] == 2
    values = [scope_time.read(ctx, s) for s in TOP_LEVEL]
    assert scope_time.read(ctx, "ssm") is None  # GPT-2 has no such scope
    total = sum(v for v in values if v is not None)
    assert total == pytest.approx(
        reduced["busy_s"] / reduced["steps"] * 1e3, rel=1e-9
    )
    # Every metric of the run, one question to the program.
    assert len(ctx["asked"]) == 1
    notes = ctx["notes"]
    assert notes["scope_matched_share"] == pytest.approx(1.0)
    assert notes["scope_description_s"] >= 0
    for scope, by_pass in notes["scope_split"].items():
        assert sum(by_pass.values()) == pytest.approx(
            scope_time.read(ctx, scope), rel=1e-9
        )
    # Remat: the blocks' forward is computed again inside the backward;
    # the head forms its gradients in its forward rule.
    split = notes["scope_split"]
    assert set(split["attn"]) == {"fwd", "bwd", "recompute"}
    assert split["head"]["fwd"] > 50 * split["head"]["bwd"]
    assert set(split["optimizer"]) == {"fwd"}


def test_top_operations_are_named_by_scope(ctx, recorded):
    reduced, _, expected = recorded
    scope_time.read(ctx, "attn")
    named = ctx["notes"]["scope_of_top_ops"]
    assert list(named) == [name for name, _ in reduced["device_ops"]]
    assert named == expected["scope_of_top_ops"]
    flash = next(n for n in named if n.startswith("flash_attention_bwd"))
    assert named[flash] == "accumulate/layers/attn bwd"
    # What each name is made of: its three longest instructions.
    longest = ctx["notes"]["scope_longest_ops"]
    assert set(longest) == set(ctx["notes"]["scope_split"])
    assert longest["attn"][0][0] == flash
    assert longest["layers"][0][0].startswith("dynamic-slice")
    for ops in longest.values():
        assert 1 <= len(ops) <= 3
        assert [ms for _, ms in ops] == sorted((ms for _, ms in ops), reverse=True)
    assert set(named.values()) <= {
        "accumulate/layers/attn fwd", "accumulate/layers/attn bwd",
        "accumulate/layers/mlp fwd", "accumulate/layers/mlp bwd",
        "accumulate/head fwd",
    }


def test_nested_reads_a_scope_wherever_it_stands():
    reduced = {
        "steps": 2,
        "ops": {
            "fusion.1": {"seconds": 0.004}, "sort.2": {"seconds": 0.002},
            "fusion.3": {"seconds": 0.006}, "copy.4": {"seconds": 0.001},
        },
        "device_ops": [["fusion.3", 0.006]],
    }
    entry = lambda scope, which: {"scope": scope, "pass": which, "op_name": "x"}  # noqa: E731
    table = scope_time.join(reduced, {
        "fusion.1": entry("accumulate/layers/mlp/moe_route", "fwd"),
        # moe.py enters the scope inside itself: counted once.
        "sort.2": entry("accumulate/layers/mlp/moe_route/moe_route", "recompute"),
        "fusion.3": entry("accumulate/layers/mlp/moe_experts", "bwd"),
        "copy.4": {"scope": "", "pass": "fwd", "op_name": ""},
    })
    ctx = {"trace": reduced, "scope_time": table}
    assert scope_time.read(ctx, "moe_route", nested=True) == pytest.approx(3.0)
    assert scope_time.read(ctx, "moe_experts", nested=True) == pytest.approx(3.0)
    assert scope_time.read(ctx, "moe_combine", nested=True) is None
    assert scope_time.read(ctx, "moe_route") is None  # not a top-level name
    assert scope_time.read(ctx, "mlp") == pytest.approx(6.0)
    assert scope_time.read(ctx, "unscoped") == pytest.approx(0.5)
    assert table["split"]["mlp"] == pytest.approx(
        {"fwd": 2.0, "recompute": 1.0, "bwd": 3.0}
    )
    assert table["inner"] == pytest.approx(
        {"moe_route": 3.0, "moe_experts": 3.0}
    )


@pytest.mark.parametrize("path,name", [
    ("accumulate/layers/attn", "attn"),
    ("accumulate/layers/mlp/moe_route", "mlp"),
    ("accumulate/layers/layers/ssm/ssm_conv", "ssm"),
    ("accumulate/layers/layers", "layers"),
    ("accumulate/layers", "layers"),
    ("accumulate/head", "head"),
    ("accumulate", "accumulate"),
    ("optimizer", "optimizer"),
    ("", "unscoped"),
])
def test_the_partition_rule(path, name):
    assert scope_time.top_level(path) == name


def test_no_trace_asks_nothing(monkeypatch):
    monkeypatch.setattr(
        scope_time, "describe", lambda: pytest.fail("asked the program")
    )
    for trace in ({}, None, {"steps": 0, "ops": {"a": {"seconds": 1.0}}}):
        assert scope_time.read({"trace": trace}, "attn") is None


def test_no_description_reads_nothing(recorded, monkeypatch):
    """The resume cell's trainer is another process, and the parent of
    the PR that brought the description has no such function."""
    from dlrover_tpu.obs import profiling

    ctx = {"trace": recorded[0]}
    monkeypatch.delattr(profiling, "compiled_scopes")
    assert scope_time.read(ctx, "attn") is None
    assert scope_time.read(ctx, "unscoped") is None
    assert "scope_split" not in ctx.get("notes", {})


def test_a_description_of_another_program_reads_nothing(recorded, monkeypatch):
    reduced, description, _ = recorded
    flash = next(n for n in description if n.startswith("flash_attention_bwd"))
    other = {k: v for k, v in description.items() if k != flash}
    monkeypatch.setattr(scope_time, "describe", lambda: other)
    ctx = {"trace": reduced}
    assert scope_time.read(ctx, "attn") is None
    assert ctx["notes"]["scope_matched_share"] < scope_time.MATCHED_AT_LEAST
    assert "scope_split" not in ctx["notes"]


@pytest.mark.parametrize("scope", [n for n in TOP_LEVEL if n != "ssm"])
def test_every_name_of_the_partition_is_a_metric_that_cells_join(scope):
    """Membership, not a count: each top-level name but ``ssm`` (read
    into ``notes.scope_split`` and no metric yet) has one file that
    names this reader, restricted to the cells that name it."""
    found = [s for s in cell_files.layer_metric_specs()
             if s["reader"] == "scope_time"
             and s["args"] == {"scope": scope}]
    assert len(found) == 1, scope
    (spec,) = found
    assert spec["restricted"] is True and "workloads" not in spec
    assert spec["unit"] == "ms" and spec["source"] == "device_trace"
    assert spec["moves"] == "tokens_per_s"


def test_an_inner_scope_s_metric_asks_for_the_nested_reading():
    inner = [s for s in cell_files.layer_metric_specs()
             if s["reader"] == "scope_time" and s["args"].get("nested")]
    assert {"moe_route", "moe_experts", "moe_combine", "mla", "kda"} <= {
        s["args"]["scope"] for s in inner
    }
    for spec in inner:
        assert spec["args"] == {"scope": spec["args"]["scope"], "nested": True}
        assert spec["args"]["scope"] not in TOP_LEVEL
        assert spec["unit"] == "ms" and spec["moves"] == "tokens_per_s"
