"""Regression tests for the perf measurement tooling: the sweep-spec
grammar and the unattended capture chain's winner selection/pinning
(tools/perf_sweep.py, tools/capture_perf.py).

Every case here is a bug class the round-5 reviews actually caught:
step-ms ranking that lets a smaller batch beat a higher-throughput
config, global-vs-per-chip batch unit confusion, env vars silently
overriding explicit spec tokens, and NaN traces winning best-of
selection in the AGD study.
"""

import importlib
import json
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

perf_sweep = importlib.import_module("perf_sweep")
capture_perf = importlib.import_module("capture_perf")


class TestBuildSpec:
    def test_positional_and_flag_tokens(self):
        cfg, attn_fn, batch, xc = perf_sweep.build_spec(
            "dots,flash,20,1024,512,u4,xc2"
        )
        assert cfg.remat == "dots"
        assert cfg.scan_unroll == 4
        assert batch == 20
        assert xc == 2

    def test_flag_tokens_position_independent(self):
        a = perf_sweep.build_spec("full,flash,18,1024,1024,xc4,u2")
        b = perf_sweep.build_spec("full,flash,18,u2,1024,1024,xc4")
        assert a[0].scan_unroll == b[0].scan_unroll == 2
        assert a[2] == b[2] == 18

    def test_explicit_xc8_beats_env(self, monkeypatch):
        """xc8 must mean 8 even when SWEEP_XENT_CHUNKS says otherwise
        — the printed result line is labeled with the spec, so the
        measured program must match it."""
        monkeypatch.setenv("SWEEP_XENT_CHUNKS", "4")
        assert perf_sweep.build_spec("full,flash,18,-,-,xc8")[3] == 8
        # absent token -> env fallback applies
        assert perf_sweep.build_spec("full,flash,18")[3] == 4

    def test_remat_token_table(self):
        for tok, name in (
            ("full", True), ("none", False), ("attn", "attention"),
            ("dots", "dots"), ("offload", "offload"),
        ):
            assert perf_sweep.build_spec(f"{tok},flash,18")[0].remat == name


class TestParseAutotune:
    OUT = (
        "n_devices: 1\n"
        "full,flash,18,1024,1024           step=  166.0ms "
        "tok/s=   111037 mfu=0.458 vs=0.924\n"
        "dots,flash,16,1024,1024,u4,xc4 step=  140.1ms "
        "tok/s=   109900 mfu=0.470 vs=0.950\n"
        "dots,flash,20,1024,1024,u4,xc4 step=  172.0ms "
        "tok/s=   119069 mfu=0.480 vs=0.960\n"
        "bogus,flash,18 FAILED: ValueError: nope\n"
    )

    def test_ranks_by_tokens_per_second_not_step_ms(self):
        spec, tok_s = capture_perf.parse_autotune(self.OUT)
        # b16 has the best step time; b20 has the best throughput —
        # throughput is what bench.py reports, so b20 must win.
        assert spec.startswith("dots,flash,20")
        assert tok_s == 119069.0

    def test_failed_lines_skipped_and_empty_is_none(self):
        assert capture_perf.parse_autotune("x FAILED: boom") is None
        assert capture_perf.parse_autotune("") is None


class TestWinnerEnv:
    def test_full_pin_set(self):
        env = capture_perf.winner_env(
            "dots,flash,20,1024,1024,u4,xc4", n_chips=1
        )
        assert env == {
            "BENCH_BLOCKS": "1024,1024,1024,1024",
            "BENCH_BATCH_PER_CHIP": "20",
            "BENCH_UNROLL": "4",
            "BENCH_XENT_CHUNKS": "4",
            "BENCH_REMAT": "dots",
        }

    def test_batch_is_global_converted_per_chip(self):
        """Sweep batch is global across its mesh; bench.py's knob is
        per-chip. A 2-chip sweep at global 40 must pin 20/chip."""
        env = capture_perf.winner_env(
            "dots,flash,40,1024,1024", n_chips=2
        )
        assert env["BENCH_BATCH_PER_CHIP"] == "20"

    def test_default_batch_not_pinned(self):
        env = capture_perf.winner_env("full,flash,18,512,1024")
        assert "BENCH_BATCH_PER_CHIP" not in env

    def test_attn_token_maps_to_policy_name(self):
        env = capture_perf.winner_env("attn,flash,18,512,1024")
        assert env["BENCH_REMAT"] == "attention"


class TestPersistWinner:
    def test_pins_only_beyond_noise_and_atomically(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setattr(capture_perf, "REPO", str(tmp_path))
        perf = tmp_path / "PERF_r05.json"
        perf.write_text(json.dumps(
            [{"stage": "baseline", "value": 100000.0}]
        ))
        pins = {"BENCH_UNROLL": "4"}
        # within noise: no file
        capture_perf.persist_winner(pins, {"value": 100300.0}, "s")
        assert not (tmp_path / "bench_tuned.json").exists()
        # beyond noise: pinned, valid JSON, no tmp litter
        capture_perf.persist_winner(pins, {"value": 101000.0}, "s")
        data = json.loads((tmp_path / "bench_tuned.json").read_text())
        assert data["pins"] == pins
        assert not (tmp_path / "bench_tuned.json.tmp").exists()

    def test_no_baseline_no_pin(self, tmp_path, monkeypatch):
        monkeypatch.setattr(capture_perf, "REPO", str(tmp_path))
        capture_perf.persist_winner({}, {"value": 1.0}, "s")
        assert not (tmp_path / "bench_tuned.json").exists()


class TestTuneCacheConsult:
    """capture_perf consults the persistent trial cache before
    spending the autotune sweep — and stays jax-free doing it."""

    def test_cached_pins_best_trial_wins(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_TUNE_CACHE", str(tmp_path / "tc.jsonl")
        )
        tc = capture_perf._load_tune_cache_mod()
        cache = tc.resolve()
        cache.record("k1", {"pins": {"BENCH_UNROLL": 4}}, 100.0)
        cache.record("k1", {"pins": {"BENCH_UNROLL": 2}}, 120.0)
        cache.record("k1", {"pins": {"BENCH_UNROLL": 8}}, None,
                     failed=True)
        assert capture_perf.cached_pins("k1") == {"BENCH_UNROLL": "2"}
        assert capture_perf.cached_pins("other") is None
        assert capture_perf.cached_pins(None) is None

    def test_no_cache_hatch_and_empty_pins(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_TUNE_CACHE", "0")
        assert capture_perf.cached_pins("k1") is None
        # a best trial with no pins (shipped defaults) is not a hit
        monkeypatch.setenv(
            "DLROVER_TPU_TUNE_CACHE", str(tmp_path / "tc2.jsonl")
        )
        tc = capture_perf._load_tune_cache_mod()
        tc.resolve().record("k1", {"pins": {}}, 50.0)
        assert capture_perf.cached_pins("k1") is None

    def test_last_recorded_tune_key_newest_wins(
        self, tmp_path, monkeypatch
    ):
        ledger = tmp_path / "ledger.jsonl"
        ledger.write_text(
            json.dumps({"metric": "m", "tune_key": "old"}) + "\n"
            + "corrupt{\n"
            + json.dumps({"metric": "m"}) + "\n"
            + json.dumps({"metric": "m", "tune_key": "new"}) + "\n"
        )
        monkeypatch.setenv("DLROVER_TPU_BENCH_LEDGER", str(ledger))
        assert capture_perf.last_recorded_tune_key() == "new"
        monkeypatch.setenv(
            "DLROVER_TPU_BENCH_LEDGER", str(tmp_path / "absent")
        )
        assert capture_perf.last_recorded_tune_key() is None

    def test_last_recorded_tune_key_prefers_tpu_baseline(
        self, tmp_path, monkeypatch
    ):
        """An ad-hoc CPU smoke bench appending the newest record must
        not hand the TPU chain its key (the chain would skip the sweep
        on pins tuned for another backend/model); CAPTURE_TUNE_KEY
        pins the choice outright."""
        ledger = tmp_path / "ledger.jsonl"
        ledger.write_text(
            json.dumps({"metric": "m", "tune_key": "tpu-base",
                        "stage": "baseline", "backend": "tpu"}) + "\n"
            + json.dumps({"metric": "m", "tune_key": "cpu-base",
                          "stage": "baseline", "backend": "cpu"}) + "\n"
            + json.dumps({"metric": "m", "tune_key": "adhoc"}) + "\n"
        )
        monkeypatch.setenv("DLROVER_TPU_BENCH_LEDGER", str(ledger))
        # newest overall is "adhoc", newest baseline is the cpu smoke
        # — the TPU baseline wins both tiebreaks
        assert capture_perf.last_recorded_tune_key() == "tpu-base"
        monkeypatch.setenv("CAPTURE_TUNE_KEY", "pinned")
        assert capture_perf.last_recorded_tune_key() == "pinned"

    def test_parent_stays_jax_free(self, tmp_path):
        """Loading + consulting the tune cache must not pull jax into
        the capture parent (which must neither hold the chip nor hang
        with it).
        Subprocess: this test process itself imports jax via
        conftest."""
        import subprocess

        src = (
            "import os, sys\n"
            f"os.environ['DLROVER_TPU_TUNE_CACHE'] = {str(tmp_path / 'tc.jsonl')!r}\n"
            f"sys.path.insert(0, {TOOLS!r})\n"
            "import capture_perf\n"
            "tc = capture_perf._load_tune_cache_mod()\n"
            "tc.resolve().record('k', {'pins': {'A': 1}}, 1.0)\n"
            "assert capture_perf.cached_pins('k') == {'A': '1'}\n"
            "assert 'jax' not in sys.modules, 'jax leaked into parent'\n"
        )
        subprocess.run(
            [sys.executable, "-c", src], check=True, timeout=60
        )


class TestLedgerPinDiff:
    def test_compare_prints_pin_diff_on_config_mismatch(
        self, tmp_path, monkeypatch
    ):
        bench_ledger = importlib.import_module("bench_ledger")
        path = str(tmp_path / "ledger.jsonl")
        base = {
            "metric": "m", "value": 100.0, "unit": "u",
            "config_hash": "aaa",
            "pins": {"BENCH_UNROLL": "1", "BENCH_REMAT": "full"},
        }
        head = {
            "metric": "m", "value": 100.0, "unit": "u",
            "config_hash": "bbb",
            "pins": {"BENCH_UNROLL": "4", "BENCH_REMAT": "full",
                     "BENCH_XENT_CHUNKS": "4"},
        }
        bench_ledger.append_record(base, path=path)
        bench_ledger.append_record(head, path=path)
        rc, report = bench_ledger.compare("last", path=path)
        assert rc == 0
        assert "pin BENCH_UNROLL: head=4 baseline=1" in report
        assert (
            "pin BENCH_XENT_CHUNKS: head=4 baseline=<unset>"
            in report
        )
        assert "BENCH_REMAT" not in report  # unchanged pins silent

    def test_compare_names_pins_only_one_side_set(self, tmp_path):
        """Against a record that pinned nothing, every pin of the
        other side is named with ``<unset>`` on the empty side, in
        both directions (a head that dropped its baseline's pins)."""
        bench_ledger = importlib.import_module("bench_ledger")
        pinned = {"BENCH_XENT_CHUNKS": "8", "BENCH_BLOCKS": "512,512"}
        for head_pins, base_pins, fmt in (
            (pinned, {}, "pin {k}: head={v} baseline=<unset>"),
            ({}, pinned, "pin {k}: head=<unset> baseline={v}"),
        ):
            path = str(tmp_path / f"ledger{len(base_pins)}.jsonl")
            for h, pins in (("aaa", base_pins), ("bbb", head_pins)):
                bench_ledger.append_record(
                    {
                        "metric": "m", "value": 100.0, "unit": "u",
                        "config_hash": h, "pins": pins,
                    },
                    path=path,
                )
            rc, report = bench_ledger.compare("last", path=path)
            assert rc == 0
            for k, v in pinned.items():
                assert fmt.format(k=k, v=v) in report

    def test_compare_same_config_no_pin_section(
        self, tmp_path
    ):
        bench_ledger = importlib.import_module("bench_ledger")
        path = str(tmp_path / "ledger.jsonl")
        rec = {
            "metric": "m", "value": 100.0, "unit": "u",
            "config_hash": "aaa", "pins": {"BENCH_UNROLL": "1"},
        }
        bench_ledger.append_record(dict(rec), path=path)
        bench_ledger.append_record(dict(rec), path=path)
        rc, report = bench_ledger.compare("last", path=path)
        assert rc == 0 and "pin " not in report


class TestBenchPinsEmission:
    def test_smoke_child_emits_pins_and_records_trial(
        self, tmp_path
    ):
        """bench.py's measurement child (BENCH_SMOKE tiny model, CPU)
        must emit the applied pins and the tune-cache key in its
        JSON record — the fields the ledger carries so compare
        mismatches are debuggable — and record the run as a cached
        trial."""
        import subprocess

        repo = os.path.dirname(TOOLS)
        cache = tmp_path / "tc.jsonl"
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_SMOKE": "1",
            "BENCH_STEPS": "2",
            "BENCH_NO_LEDGER": "1",
            "BENCH_XENT_CHUNKS": "4",
            "DLROVER_TPU_TUNE_CACHE": str(cache),
        }
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--child"],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=repo,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        rec = next(
            json.loads(line)
            for line in p.stdout.splitlines()
            if line.startswith("{")
        )
        assert rec["pins"] == {"BENCH_XENT_CHUNKS": "4"}
        assert "overlap" not in rec
        assert rec["tune_key"]
        assert rec["value"] > 0
        trials = [
            json.loads(line) for line in cache.read_text().splitlines()
        ]
        assert len(trials) == 1
        assert trials[0]["key"] == rec["tune_key"]
        assert trials[0]["config"] == {"pins": rec["pins"]}
        assert not trials[0]["failed"]


class TestBenchPrefetchSmoke:
    def test_smoke_child_prefetch_record(self, tmp_path):
        """bench.py's child fed by the Prefetcher (BENCH_PREFETCH=1:
        fresh host batches, placed by the worker): the record carries
        a data_wait_s figure and nothing of the retired options."""
        import subprocess

        repo = os.path.dirname(TOOLS)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "BENCH_SMOKE": "1",
            "BENCH_STEPS": "2",
            "BENCH_NO_LEDGER": "1",
            "BENCH_PREFETCH": "1",
            "DLROVER_TPU_TUNE_CACHE": "0",
        }
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py"),
             "--child"],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=repo,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        rec = next(
            json.loads(line)
            for line in p.stdout.splitlines()
            if line.startswith("{")
        )
        assert rec["value"] > 0
        assert rec["data_wait_s"] >= 0
        assert "pipeline" not in rec and rec["pins"] == {}


class TestAGDTraceSelection:
    def test_nan_trace_never_wins(self):
        """agd_convergence's best-trace guard: a diverged (NaN) final
        loss must not beat a finite one via NaN-compare semantics."""
        agd = importlib.import_module("agd_convergence")
        runs = {
            3e-5: [(5, 10.0), (10, float("nan"))],
            6e-5: [(5, 10.5), (10, 9.8)],
        }
        lr, tr = agd.best_finite_trace(runs)
        assert lr == 6e-5 and tr[-1][1] == 9.8

    def test_all_diverged_still_returns(self):
        agd = importlib.import_module("agd_convergence")
        runs = {1e-3: [(5, float("nan"))]}
        assert agd.best_finite_trace(runs)[0] == 1e-3


def test_the_changelog_keeps_every_entry():
    """A PR adds a line to CHANGES.md and removes none (ROADMAP D20:
    PR 55's commit cut twelve). Every line is one PR's entry, and the
    PRs that had one when PR 56 restored them still do."""
    import re

    root = os.path.dirname(TOOLS)
    with open(os.path.join(root, "CHANGES.md")) as f:
        lines = f.read().splitlines()
    numbers = [re.match(r"- PR (\d+) \(\w+\)", line) for line in lines]
    assert all(numbers), [
        line[:60] for line, n in zip(lines, numbers) if not n
    ]
    had = {int(n.group(1)) for n in numbers}
    assert had >= {
        1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 12, 13, 14, 15, 16, 19, 20, 21,
        23, 24, 25, 26, 27, 28, 29, 33, 34, 35, 37, 39, 42, 44, 45, 47,
        48, 51, 52, 53, 54, 55, 56,
    }
    assert len(lines) >= 42
