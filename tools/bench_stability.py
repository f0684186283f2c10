"""Multi-run bench stability record (VERDICT r4 weak #1).

Runs ``bench.py`` N times (default 3) back-to-back on the live chip and
writes STABILITY_r05.json with every run's record plus mean / stddev /
spread of tokens-per-second, so single-run sweep deltas (e.g. 0.902 vs
0.924 in PERF_r04.json) can be judged against measured run-to-run noise.

Artifact is written ONLY if >= ``--min-runs`` runs succeed, so an outage
mid-way leaves no misleading single-run "stability" file and the
unattended chain retries on its next probe.

Run:  python -u tools/bench_stability.py
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(timeout_s: float) -> dict | None:
    try:
        p = subprocess.run(
            [sys.executable, "bench.py"],
            env={**os.environ, "BENCH_MAX_WAIT_S": "600",
                 "BENCH_PROBE_TIMEOUT": "90"},
            capture_output=True, text=True, cwd=REPO, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        print(f"[stability] run timed out after {timeout_s:.0f}s", flush=True)
        return None
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                return None
            if not rec.get("error"):
                return rec
    print(f"[stability] rc={p.returncode} stderr tail: "
          f"{(p.stderr or '')[-300:]}", flush=True)
    return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--min-runs", type=int, default=3)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args()

    runs = []
    for i in range(args.runs):
        print(f"[stability] run {i + 1}/{args.runs}", flush=True)
        rec = one_run(args.timeout)
        if rec:
            rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
            runs.append(rec)
            print(f"[stability] -> {rec['value']} {rec.get('unit')}",
                  flush=True)
        time.sleep(5)

    if len(runs) < args.min_runs:
        print(f"[stability] only {len(runs)}/{args.min_runs} runs landed; "
              "not writing artifact", flush=True)
        return 1

    vals = [r["value"] for r in runs]
    mean = sum(vals) / len(vals)
    var = (
        sum((v - mean) ** 2 for v in vals) / (len(vals) - 1)
        if len(vals) > 1 else 0.0
    )
    stats = {
        "n": len(vals),
        "mean": round(mean, 1),
        "stddev": round(math.sqrt(var), 1),
        "spread_pct": round(100 * (max(vals) - min(vals)) / mean, 3),
    }
    out = {
        "runs": runs,
        **stats,
        "unit": runs[0].get("unit"),
        "vs_baseline_mean": round(
            sum(r.get("vs_baseline", 0) for r in runs) / len(runs), 4),
    }
    # Provenance stamp + ledger record: the multi-run stats are the
    # strongest comparison endpoint the regression gate can use
    # (tools/bench_ledger.py prefers stats.mean over single values).
    try:
        import _repo_path  # noqa: F401
        from dlrover_tpu.common.runmeta import run_metadata

        out["meta"] = run_metadata(
            backend=runs[0].get("backend")
        )
        import bench_ledger

        bench_ledger.append_record(
            {
                "metric": runs[0].get("metric"),
                "value": stats["mean"],
                "unit": runs[0].get("unit"),
                "vs_baseline": out["vs_baseline_mean"],
                "stats": stats,
                "stage": "stability",
                "meta": out["meta"],
            }
        )
    except Exception as exc:  # noqa: BLE001 — bookkeeping must not
        # discard three successful chip runs
        print(f"[stability] ledger/meta stamp failed: {exc!r}",
              flush=True)
    path = os.path.join(REPO, "STABILITY_r05.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[stability] wrote {path}: mean={out['mean']} "
          f"stddev={out['stddev']} spread={out['spread_pct']}%", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
