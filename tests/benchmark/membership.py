"""What a cell's own test asks of the manifest and the files: that the
cell and its metrics are **in** them. Never that they are last, the
only ones, or exactly N: the next cell appends its name to the same
lists, and a test that pinned a list by equality would turn red for a
PR that did nothing wrong (``test_cells_cpu.py`` asserts the
manifest's limits and its agreement with the files, once)."""

import json
import os

from benchmark import cell as cell_files


def manifest() -> dict:
    with open(os.path.join(cell_files.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def assert_cell_is_listed(man: dict, cell: str, traffic: str = "steady",
                          chips: int = 1,
                          end_to_end=("tokens_per_s", "step_ms_p90")) -> dict:
    """The cell's entry, after: it is there once, with its traffic and
    chips, its configuration has an entry, and each end-to-end metric
    named lists it (``setup_s`` lists none, so every cell reports it)."""
    (entry,) = [w for w in man["workloads"] if w["name"] == cell]
    assert (entry["traffic"], entry["chips"]) == (traffic, chips)
    assert entry["config"] in {c["name"] for c in man["configs"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for name in end_to_end:
        assert cell in e2e[name]["workloads"], name
    assert "workloads" not in e2e["setup_s"]
    return entry


def assert_cell_reports(man: dict, cell: str, metric: str) -> dict:
    """The metric's file, after: the manifest has the metric and lists
    the cell in it (or lists none), the cell's workload file names it
    where it is restricted, and ``cell.cell_metric_specs`` gives it."""
    (entry,) = [m for m in man["per_layer"] if m["name"] == metric]
    assert cell in entry.get("workloads", [cell]), (metric, cell)
    loaded = cell_files.load_cell(cell)
    (spec,) = [s for s in cell_files.cell_metric_specs(loaded)
               if s["name"] == metric]
    assert bool(spec.get("restricted")) == ("workloads" in entry)
    if spec.get("restricted"):
        assert metric in loaded["workload"]["per_layer"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], (metric, key)
    return spec


TOP_SCOPES = ("attn", "mlp", "head", "optimizer", "unscoped", "embed")
INNER_SCOPES = ("kda", "kda_scan", "mla", "mla_rope", "moe_shared",
                "moe_routed", "moe_route", "moe_experts", "moe_combine",
                "attn_window", "attn_full", "exit_gate")
KERNEL_EVENTS = {"flash_fwd": "^flash_attention_fwd",
                 "flash_bwd": "^flash_attention_bwd",
                 "moe_gmm": "^moe_gmm", "moe_tgmm": "^moe_tgmm"}
PER_STEP_MS = {
    "moe_gmm": {"name": "^moe_t?gmm"}, "moe_tgmm": {"name": "^moe_tgmm"},
    "flash_bwd": {"name": "^flash_attention_bwd"},
    "pallas": {"category": "tpu_custom_call"},
}


def assert_reads_as_its_copy_did(spec: dict) -> None:
    """The generic file reads what the cell's own copy of it read
    before PR 63 folded the copies: ``scope_time`` where the copy
    went through a reader that was that one under another name, one
    ``kernel_work`` module a kernel where it named a variant."""
    name = spec["name"]
    reading = (spec["reader"], spec.get("args", {}))
    head = name.split("_ms_per_step")[0]
    if name == "mfu.train":
        assert reading == ("model_flops", {})
    elif name.endswith("_roofline.train"):
        kernel = name[: -len("_roofline.train")]
        assert spec["unit"] == "%" and reading == ("trace_events", {
            "what": "roofline", "kernel": kernel,
            "name": KERNEL_EVENTS[kernel],
        })
    elif head in TOP_SCOPES:
        assert reading == ("scope_time", {"scope": head})
    elif head in INNER_SCOPES:
        assert reading == ("scope_time", {"scope": head, "nested": True})
    elif head in PER_STEP_MS:
        assert reading == (
            "trace_events", dict(PER_STEP_MS[head], what="per_step_ms"))
    elif name in ("data_wait_ms.train", "dispatch_ms.train"):
        assert reading == ("host_timer", {"field": name[: -len(".train")]})
    elif name in ("step_programs.train", "step_hbm_gb.train"):
        assert spec["reader"] == "count"
    else:
        assert name.endswith(".setup") and spec["reader"] == "startup", name
