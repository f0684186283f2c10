"""``setup_s`` from the inside (ISSUE 55): reader ``startup`` on
hand-made marks and records, one traced steady rehearsal on the CPU
whose five parts partition the start, and the fourteen metric files
against the manifest."""

import json
import os
import types

import pytest

from benchmark import cell as cell_files
from benchmark.readers import startup
from tests.benchmark.test_cells_cpu import _last_line, _run

PARTS = ("boot_s.setup", "runtime_init_s.setup", "state_init_s.setup",
         "pre_step_s.setup", "first_step_s.setup")
STAGES = ("step_trace_lower_s.setup", "trace_lower_s.setup",
          "compile_s.setup", "cache_load_s.setup", "compile_requests.setup",
          "price_step_s.setup")
LAUNCHER = ("count_chips_s.setup", "master_start_s.setup", "spawn_s.setup")
RESUME = "gpt2-124m.resume"
STEADY_NOW = ("gpt2-124m.steady", "mistral-7b.steady",
              "mistral-7b-host4.fsdp4", "olmoe-1b-7b.steady",
              "granite-4.0-h-micro.steady", "ouro-2.6b.steady")

# The phases file after the resume cell's one restart: the first
# launch under ``prev.``, the relaunch under the plain names.
FIRST = {"agent.launch_start": 100.0, "agent.chips_counted": 108.0,
         "agent.master_ready": 109.5, "agent.spawned": 110.0,
         "proc_start": 110.25, "dist_ready": 113.0, "devices_ready": 119.0,
         "accelerate_done": 119.5, "built": 121.0, "restore_done": 121.25,
         "first_dispatch": 124.0, "first_step_done": 131.0}
SECOND = {"agent.exit_seen": 190.0, "agent.spawned": 190.5,
          "proc_start": 191.0, "dist_ready": 192.0, "devices_ready": 195.0,
          "built": 196.0, "first_dispatch": 197.0, "first_step_done": 198.0}
TWO_ATTEMPTS = {**{"prev." + k: v for k, v in FIRST.items()}, **SECOND,
                "kill": 189.0}


def _spec(name):
    (spec,) = [s for s in cell_files.layer_metric_specs() if s["name"] == name]
    assert spec["reader"] == "startup"
    return spec


def _read(name, ctx):
    return startup.read(ctx, **_spec(name)["args"])


@pytest.mark.parametrize("name,want", [
    ("boot_s.setup", 2.75), ("runtime_init_s.setup", 6.0),
    ("state_init_s.setup", 2.0), ("pre_step_s.setup", 3.0),
    ("first_step_s.setup", 7.0), ("count_chips_s.setup", 8.0),
    ("master_start_s.setup", 1.5), ("spawn_s.setup", 0.75),
])
def test_marks_of_two_attempts_resolve_to_the_first_launch(name, want):
    ctx = {"marks": dict(TWO_ATTEMPTS)}
    assert _read(name, ctx) == pytest.approx(want)
    # One launch and no restart: the plain names are the first launch.
    assert _read(name, {"marks": dict(FIRST)}) == pytest.approx(want)
    notes = ctx["notes"]["setup_marks"]
    assert notes["proc_start"] == 0.0
    assert notes["agent.launch_start"] == pytest.approx(-10.25)
    assert notes["first_step_done"] == pytest.approx(20.75)


def test_the_five_parts_partition_the_first_launch():
    ctx = {"marks": dict(TWO_ATTEMPTS)}
    assert sum(_read(name, ctx) for name in PARTS) == pytest.approx(
        FIRST["first_step_done"] - FIRST["proc_start"]
    )


@pytest.mark.parametrize("name,gone", [
    ("pre_step_s.setup", "first_dispatch"),
    ("first_step_s.setup", "first_dispatch"),
    ("count_chips_s.setup", "agent.chips_counted"),
    ("master_start_s.setup", "agent.chips_counted"),
    ("boot_s.setup", "proc_start"),
])
def test_a_missing_mark_reads_none_never_zero(name, gone):
    marks = {k: v for k, v in FIRST.items() if k != gone}
    assert _read(name, {"marks": marks}) is None


@pytest.mark.parametrize("name", STAGES)
def test_stage_metrics_read_none_where_the_trainer_was_another_process(name):
    assert _read(name, {"marks": dict(TWO_ATTEMPTS)}) is None


def test_stage_records_are_cut_at_the_mark_matched_by_name_and_not_counted_twice(
    monkeypatch,
):
    records = [
        {"stage": "trace", "fn": "_init", "t0": 1.0, "t1": 2.0},
        {"stage": "lower", "fn": "jit__init", "t0": 2.0, "t1": 2.5},
        {"stage": "backend_compile", "fn": "jit__init", "t0": 2.5, "t1": 4.5},
        # The pricing: the step traced (a jitted block inside it) and
        # lowered; then the dispatch loads it from the cache.
        {"stage": "trace", "fn": "block", "t0": 10.5, "t1": 11.0},
        {"stage": "trace", "fn": "train_step", "t0": 10.0, "t1": 12.0},
        {"stage": "lower", "fn": "jit_train_step", "t0": 12.0, "t1": 13.0},
        {"stage": "price", "fn": "train_step", "t0": 9.9, "t1": 13.1},
        {"stage": "cache_load", "fn": "jit_train_step", "t0": 13.5, "t1": 14.0},
        # After first_step_done: compiled_scopes' lowering, not a start's.
        {"stage": "lower", "fn": "jit_train_step", "t0": 30.0, "t1": 31.0},
        {"stage": "cache_load", "fn": "jit_train_step", "t0": 31.0, "t1": 31.5},
    ]
    marks = {"proc_start": 0.0, "first_step_done": 20.0}
    program = types.SimpleNamespace(
        startup_timeline=lambda: {"marks": marks, "compile": records},
        union_seconds=startup._program().union_seconds,
    )
    monkeypatch.setattr(startup, "_program", lambda: program)
    ctx = {"marks": {}}
    got = {name: _read(name, ctx) for name in STAGES}
    assert got == {
        "step_trace_lower_s.setup": pytest.approx(3.0),
        "trace_lower_s.setup": pytest.approx(4.5),
        "compile_s.setup": pytest.approx(2.0),
        "cache_load_s.setup": pytest.approx(0.5),
        "compile_requests.setup": 2,
        "price_step_s.setup": pytest.approx(3.2),
    }
    assert ctx["notes"]["setup_compile_by_fn"] == {
        "jit__init": {"n": 1, "seconds": 2.0, "backend_compile": 1},
        "jit_train_step": {"n": 1, "seconds": 0.5, "cache_load": 1},
    }
    # A program older than the timeline (the parent of this PR).
    monkeypatch.setattr(startup, "_program", lambda: None)
    assert _read("compile_s.setup", {"marks": {}}) is None
    assert _read("boot_s.setup", {"marks": {}}) is None


def test_many_functions_are_listed_by_seconds_and_the_rest_summed():
    records = [{"stage": "cache_load", "fn": f"f{i}", "t0": 0.0, "t1": 1.0 + i}
               for i in range(startup.LISTED + 3)]
    table = startup.by_function(records)
    assert len(table) == startup.LISTED + 1
    assert list(table)[0] == f"f{startup.LISTED + 2}"
    assert table[startup.OTHERS] == {"n": 3, "seconds": 6.0, "cache_load": 3}


def test_traced_steady_rehearsal_splits_its_start_in_five():
    line = _last_line(_run("toy-gpt.steady", 1, trace=1))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(PARTS + STAGES) <= set(m)
    assert not set(LAUNCHER) & set(m)  # no launcher in a steady cell
    parts = [m[name] for name in PARTS]
    assert all(v >= 0 for v in parts)
    marks = line["notes"]["setup_marks"]
    assert marks["proc_start"] == 0.0
    assert sum(parts) == pytest.approx(marks["first_step_done"], abs=0.05)
    order = ["proc_start", "dist_ready", "devices_ready", "accelerate_done",
             "built", "first_dispatch", "first_step_done"]
    assert [marks[k] for k in order] == sorted(marks[k] for k in order)
    assert 0 < m["step_trace_lower_s.setup"] <= (
        m["first_step_s.setup"] + m["pre_step_s.setup"]
    )
    assert m["step_trace_lower_s.setup"] <= m["trace_lower_s.setup"]
    assert 0 < m["price_step_s.setup"] <= m["first_step_s.setup"]
    assert m["compile_s.setup"] + m["cache_load_s.setup"] > 0
    by_fn = line["notes"]["setup_compile_by_fn"]
    assert sum(row["n"] for row in by_fn.values()) == m["compile_requests.setup"]
    (step,) = [row for fn, row in by_fn.items() if "train_step" in fn]
    assert step["n"] >= 1
    # The accepted metrics are where they were.
    assert m["step_programs.train"] == 1


# -- the manifest ------------------------------------------------------


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(cell_files.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", PARTS + STAGES + LAUNCHER)
def test_each_new_metric_file_has_its_entry_and_lists_cells_of_the_manifest(
    manifest, name
):
    spec = _spec(name)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]
    assert ("workloads" in entry) == bool(spec.get("restricted"))
    assert "workloads" not in spec  # the cells say it, not the metric
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "bootstrap", "setup_s", "lower")
    cells = [w["name"] for w in manifest["workloads"]]
    steady = {c for c in cells if c != RESUME}
    if name in PARTS:
        # No list: wherever ``setup_s`` is reported, later cells too.
        assert "workloads" not in entry
    elif name in STAGES:
        # (A member test: a later steady cell may append itself.)
        assert set(STEADY_NOW) <= set(entry["workloads"]) <= steady
    else:
        assert RESUME in entry["workloads"]
    assert set(entry.get("workloads", cells)) <= set(cells)
    # What the manifest lists is what the cells' own files name.
    for cell in entry.get("workloads", []):
        assert name in cell_files.load_cell(cell)["workload"]["per_layer"]


def test_the_new_entries_are_there_and_the_old_are_as_they_were(manifest):
    # (Members, wherever they stand and whatever stands between them.)
    names = [m["name"] for m in manifest["per_layer"]]
    assert set(PARTS + STAGES + LAUNCHER) <= set(names)
    # The five marks metrics of the restart stay, with their cell.
    for name in ("bootstrap_s.resume", "runtime_init_s.resume",
                 "accelerate_s.resume", "state_init_s.resume",
                 "resume_cache_misses.resume"):
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert RESUME in entry["workloads"] and entry["moves"] == "setup_s"
