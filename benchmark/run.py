"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by name (``workloads/``, ``configs/``,
``traffic/``), hands the cell to the module of its traffic kind
(``kinds/``), and prints one JSON object as the last line of standard
output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics (those every cell
reports and those its workload file names under ``per_layer``), each
read by the reader its file under ``layer_metrics/`` names. No TPU, or
another count of chips than the cell's, is a non-zero exit and no
result.

``--cells-root`` and ``--allow-cpu`` are the CPU rehearsal's
(tests/benchmark/test_cells_cpu.py): toy cells from ``testdata/``, and
a result that names the CPU as its device.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
# libtpu logs under /tmp unless told: keep them inside the checkout.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(REPO, ".cache", "tpu_logs"))


def layer_metrics(cell: dict, result: dict) -> dict:
    from benchmark import cell as cell_files
    from benchmark import peaks

    ctx = dict(result["ctx"], cell=cell, device=result["device"])
    try:
        ctx["peaks"] = peaks.chip_peaks(result["device"]["kind"])
    except KeyError:
        if result["device"]["platform"] == "tpu":
            raise
        ctx["peaks"] = None  # a rehearsal: readers of a peak read nothing
    out = {}
    specs = cell_files.cell_metric_specs(cell)
    for spec in specs:
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    # The cell's metrics whose reader found nothing to read: off the
    # chip most of them; on it, a name the cell should not have listed.
    ctx.setdefault("notes", {})["read_nothing"] = sorted(
        s["name"] for s in specs if s["name"] not in out
    )
    result["notes"] = ctx["notes"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cells-root", default=HERE)
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--dump-events", default="",
                   help="also write the trace's events to this .json.gz")
    p.add_argument("--keep-work", action="store_true")
    p.add_argument("--deadline-s", type=float, default=1100.0)
    args = p.parse_args(argv)

    from benchmark import cell as cell_files

    if not os.path.isdir(os.path.join(REPO, "dlrover_tpu")):
        print(f"[bench] {REPO} holds no dlrover_tpu package: there is "
              "no system to measure", file=sys.stderr)
        return 2
    try:
        cell = cell_files.load_cell(args.workload, args.cells_root)
        kind = importlib.import_module(
            f"benchmark.kinds.{cell['traffic']['kind']}"
        )
        result = kind.run(cell, {
            "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace), "allow_cpu": args.allow_cpu,
            "t_start": T_START, "dump_events": args.dump_events,
            "keep_work": args.keep_work, "deadline_s": args.deadline_s,
        })
    except Exception as exc:  # noqa: BLE001 - any failure: no result line
        import traceback

        traceback.print_exc()
        print(f"[bench] FAILED, no result: {exc}", file=sys.stderr, flush=True)
        return 1

    red = (result["ctx"].get("trace") or {}) if args.trace else {}
    if args.trace:
        metrics = layer_metrics(cell, result)
        if red:
            result["device"]["busy_s"] = red["busy_s"]
            result["device"]["window_s"] = red["window_s"]
    else:
        metrics = {
            name: {"value": result["values"][name], "unit": unit}
            for name, unit in cell["traffic"]["end_to_end"].items()
            if result["values"].get(name) is not None
        }
    line = {
        "correct": bool(result["correct"]),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": result["device"],
        "workload": cell["name"],
        "seed": args.seed,
        "why_incorrect": result["why"],
        "detail": result["detail"],
        "notes": result.get("notes", {}),
    }
    if red:
        line["breakdown"] = {
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
        }
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
