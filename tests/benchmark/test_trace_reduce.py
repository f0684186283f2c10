"""The yardstick's arithmetic: the trace reducer on hand-made events
and on a small trace recorded on the chip, the operation counts
against hand-worked numbers, and the step statistics."""

import json
import os

import pytest

from benchmark import cell as cell_files
from benchmark import flops, step_metrics
from benchmark import trace_reduce as tr

TESTDATA = os.path.join(cell_files.HERE, "testdata")
DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV, category=""):
    return {"plane": plane, "line": line, "name": name, "start": float(start),
            "dur": float(dur), "category": category}


def _four_steps(plane=DEV):
    """Four executions of one step program, 1000 ns apart, 900 long:
    a 500 ns while enclosing a fusion and a kernel, an all-gather that
    nothing overlaps, a last fusion; 100 ns idle between steps."""
    out = []
    for i in range(4):
        b = i * 1000
        out += [
            _ev(tr.MODULES_LINE, "jit_train_step", b, 900, plane),
            _ev(tr.OPS_LINE, "while.1", b, 500, plane),
            _ev(tr.OPS_LINE, "fusion.1", b + 10, 200, plane),
            _ev(tr.OPS_LINE, "flash_attention_fwd", b + 220, 100, plane,
                "custom-call"),
            _ev(tr.OPS_LINE, "all-gather.1", b + 500, 100, plane),
            _ev(tr.OPS_LINE, "fusion.2", b + 600, 300, plane),
        ]
    return out


def test_interval_arithmetic():
    assert tr.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert tr.total([[0, 3], [5, 8]]) == 6
    assert tr.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 8]]) == [[0, 2], [3, 5], [8, 10]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def test_self_time_takes_children_off_their_parent():
    selfs = {e["name"]: s for e, s in tr.self_times([
        _ev(tr.OPS_LINE, "while", 0, 100),
        _ev(tr.OPS_LINE, "a", 10, 30),
        _ev(tr.OPS_LINE, "b", 50, 40),
        _ev(tr.OPS_LINE, "b.inner", 55, 10),
        _ev(tr.OPS_LINE, "after", 100, 5),
    ])}
    assert selfs == {"while": 30, "a": 30, "b": 30, "b.inner": 10, "after": 5}


def test_reduce_steady_window_busy_idle_and_gaps():
    events = _four_steps() + [
        _ev("python", "bench.next_batch", 1890, 120, "/host:CPU"),
        _ev("python", "not.ours", 0, 5000, "/host:CPU"),
    ]
    red = tr.reduce(events)
    # From the start of the second execution to the end of the last.
    assert red["steps"] == 3 and red["step_module"] == "jit_train_step"
    assert red["window_s"] == pytest.approx(2900e-9)
    assert red["busy_s"] == pytest.approx(2700e-9)
    # Time is counted once: the while keeps what its body leaves.
    assert red["ops"]["while.1"]["seconds"] == pytest.approx(600e-9)
    assert red["ops"]["while.1"]["total_seconds"] == pytest.approx(1500e-9)
    assert sum(v["seconds"] for v in red["ops"].values()) == pytest.approx(
        red["busy_s"]
    )
    assert red["device_ops"][0] == ["fusion.2", pytest.approx(900e-9)]
    # Nothing runs beside the all-gather: all of it is exposed.
    assert red["collective_s"] == pytest.approx(300e-9)
    assert red["collective_exposed_s"] == pytest.approx(300e-9)
    # Two 100 ns gaps: one while the host sat in next(batches).
    assert dict(red["idle_gaps"]) == {
        "bench.next_batch": pytest.approx(100e-9),
        "unannotated": pytest.approx(100e-9),
    }
    kernels = tr.matching_ops(red, category_pattern="custom-call")
    assert [n for n, _ in kernels] == ["flash_attention_fwd"]


def test_asynchronous_collective_exposes_only_its_own_events():
    """Device 1 gathers asynchronously: a 10 ns start, 150 ns of
    compute, a 40 ns done. In flight for 200 ns, exposed for 50."""
    events = _four_steps()
    for e in _four_steps("/device:TPU:1"):
        if e["name"] != "all-gather.1":
            events.append(e)
    for i in range(4):
        b = i * 1000
        events += [
            _ev(tr.OPS_LINE, "all-gather-start.1", b + 400, 10, "/device:TPU:1"),
            _ev(tr.OPS_LINE, "all-gather-done.1", b + 560, 40, "/device:TPU:1"),
        ]
    red = tr.reduce(events)
    assert red["n_devices"] == 2
    # Means over the two devices, three steps in the window.
    assert red["collective_s"] == pytest.approx((300e-9 + 600e-9) / 2)
    assert red["collective_exposed_s"] == pytest.approx((300e-9 + 150e-9) / 2)


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce([_ev("python", "bench.train_step", 0, 5, "/host:CPU")]) == {}


def test_recorded_trace_from_the_chip(tmp_path):
    """A few steps of gpt2-124m.steady recorded on a v5e (PR 23) and
    cut to the events of its steady window."""
    path = os.path.join(TESTDATA, "gpt2_steady_v5e.events.json.gz")
    events = tr.load_events(path)
    red = tr.reduce(events)
    with open(os.path.join(TESTDATA, "gpt2_steady_v5e.expected.json")) as f:
        want = json.load(f)
    assert red["steps"] == want["steps"]
    assert red["n_devices"] == 1
    for key in ("window_s", "busy_s", "collective_s"):
        assert red[key] == pytest.approx(want[key], rel=1e-9, abs=1e-12)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert sum(v["seconds"] for v in red["ops"].values()) == pytest.approx(
        red["busy_s"], rel=1e-6
    )
    assert [n for n, _ in red["device_ops"][:3]] == want["top3"]
    flash = tr.matching_ops(red, name_pattern=want["flash_pattern"])
    assert flash and sum(r["count"] for _, r in flash) == pytest.approx(
        want["flash_calls"]
    )
    # The dump is its own round trip.
    again = str(tmp_path / "again.events.json.gz")
    tr.dump_events(events, again)
    assert tr.load_events(again) == events


def _config(name):
    with open(os.path.join(cell_files.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mean_keys():
    assert flops.mean_keys(1024) == 512.5
    assert flops.mean_keys(8192, 4096) == 3072.25
    assert flops.mean_keys(8, 100) == 4.5
    # By enumeration: query i sees min(i + 1, window) keys.
    assert flops.mean_keys(10, 3) == sum(min(i + 1, 3) for i in range(10)) / 10


def test_gpt2_124m_needs_798_mflop_a_token():
    cfg = _config("gpt2-124m")
    # 12 layers x 12 x 768^2 + the 50304 x 768 loss head.
    assert flops.matmul_params(cfg) == 84_934_656 + 38_633_472
    # 12 x 12 layers x 768 x 512.5 keys.
    assert flops.attention_flops_per_token(cfg) == 56_678_400
    assert flops.train_flops_per_token(cfg) == 798_087_168


def test_mistral_two_layers_need_3_7_gflop_a_token():
    cfg = _config("mistral-7b")
    assert cfg["num_hidden_layers"] == 2
    # wq, wo 4096^2; wk, wv 4096 x 1024; gate, up, down 4096 x 14336.
    layer = 2 * 4096 ** 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.matmul_params(cfg) == 2 * layer + 32000 * 4096 == 567_279_616
    assert flops.attention_flops_per_token(cfg) == 302_014_464
    assert flops.train_flops_per_token(cfg) == 3_705_692_160


def test_flash_forward_call_and_roofline():
    work = flops.kernel_work("flash_fwd", _config("gpt2-124m"), 18)
    assert work["flops"] == 4 * 18 * 12 * 64 * 1024 * 512.5
    assert work["bytes"] == 4 * 18 * 1024 * 768 * 2 + 18 * 12 * 1024 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops.roofline_seconds(work, peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(work["flops"] / 197e12)
    assert flops.roofline_seconds(
        {"flops": 1.0, "bytes": 1e9}, peaks
    )["bound"] == "memory"


def test_a_family_or_kernel_is_found_by_its_name_and_needs_no_jax():
    # The resume cell's parent counts operations and must stay off JAX:
    # a child that imports the counts may not have imported jax.
    import subprocess
    import sys

    code = (
        "import json, sys; from benchmark import flops; "
        "c = json.load(open(sys.argv[1])); "
        "print(flops.train_flops_per_token(c), "
        "flops.kernel_work('flash_fwd', c, 1)['flops'], 'jax' in sys.modules)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code,
         os.path.join(cell_files.HERE, "configs", "mistral-7b.json")],
        cwd=cell_files.REPO, capture_output=True, text=True, check=True,
    ).stdout.split()
    assert float(out[0]) == 3_705_692_160 and out[2] == "False"
    assert float(out[1]) == 4 * 32 * 128 * 8192 * 3072.25
    with pytest.raises(ModuleNotFoundError):
        flops.shape_of({"family": "no_such_family"})
    with pytest.raises(ModuleNotFoundError):
        flops.kernel_work("no_such_kernel", _config("gpt2-124m"), 1)


@pytest.mark.parametrize("name", ["gpt2-124m", "mistral-7b", "mistral-7b-host4"])
def test_every_family_gives_the_same_shape_keys(name):
    assert set(flops.shape_of(_config(name))) == {
        "layers", "embd", "heads", "kv_heads", "head_dim", "vocab_rows",
        "seq_len", "window", "layer_matmul_params",
    }


def test_reference_error_is_not_shrunk_by_cancelling():
    from benchmark.kinds import common

    err = common.reference_error([10.01, 9.99], [10.0, 10.0])
    assert err["mean_rel"] == pytest.approx(0.0, abs=1e-12)
    assert err["rms_rel"] == pytest.approx(1e-3)
    assert not common.reference_ok(err)
    assert common.reference_ok(common.reference_error([10.001], [10.0]))
    assert not common.reference_ok({})


def test_committed_step_reads_the_tracker(tmp_path):
    from benchmark.kinds import save_kill_resume as skr
    from dlrover_tpu.trainer.flash_checkpoint import engine

    assert skr.committed_step(str(tmp_path)) == -1
    (tmp_path / engine.TRACKER_FILE).write_text("200\n")
    assert skr.committed_step(str(tmp_path)) == 200


def test_peaks_table_refuses_an_unknown_device():
    from benchmark import peaks

    assert peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.chip_peaks("_source")


def _steps(times, saves=()):
    return [
        {"step": i + 1, "t_done": t, "loss": 5.0 - 0.01 * i,
         "data_wait_s": 0.001, "dispatch_s": 0.002,
         **({"save": {}} if i in saves else {})}
        for i, t in enumerate(times)
    ]


def test_window_metrics_rate_tail_and_stall():
    # 40 steps 0.1 s apart; steps 10 and 30 carried a save of 0.3 s.
    times, t = [], 0.0
    for i in range(40):
        t += 0.4 if i in (10, 30) else 0.1
        times.append(t)
    m = step_metrics.window_metrics(0.0, _steps(times, (10, 30)), 1000, 2)
    assert m["steps"] == 40
    assert m["window_s"] == pytest.approx(4.6)
    assert m["tokens_per_s"] == pytest.approx(40 * 1000 / 4.6)
    assert m["step_ms_median"] == pytest.approx(100.0)
    assert m["saves"] == 2
    assert m["save_stall_ms"] == pytest.approx(300.0)
    # 19 pairs of steps, two of which hold a save: (0.1 + 0.4) / 2.
    assert m["step_samples"] == 19
    assert m["step_ms_p90"] == pytest.approx(130.0, abs=31.0)
    assert m["data_wait_ms"] == pytest.approx(1.0)
    assert m["dispatch_ms"] == pytest.approx(2.0)


def test_a_dropped_save_is_counted_and_is_neither_stall_nor_plain_step():
    times = [0.0, 0.1, 0.2, 0.7, 0.8, 0.9, 1.2, 1.3, 1.4]
    steps = _steps(times, saves=(3, 6))
    steps[6]["save"] = {"save_ok": False}
    m = step_metrics.window_metrics(0.0, steps, 100)
    assert m["saves"] == 1 and m["saves_dropped"] == 1
    assert "save_call_ms" not in m  # these records carry no save_s
    steps[3]["save"] = {"save_ok": True, "save_s": 0.35}
    m = step_metrics.window_metrics(0.0, steps, 100)
    assert m["save_call_ms"] == pytest.approx(350.0)
    # The longest intervals first, with what the host spent in them.
    assert [s["step"] for s in m["longest_steps"]][:2] == [4, 7]
    assert len(m["longest_steps"]) == 3
    assert m["longest_steps"][0]["ms"] == pytest.approx(500.0)
    assert m["longest_steps"][0]["save_s"] == 0.35
    assert m["longest_steps"][0]["dispatch_ms"] == pytest.approx(2.0)
    assert m["save_stall_ms"] == pytest.approx(400.0)
    assert m["step_ms_median"] == pytest.approx(100.0)
    assert "saves_dropped" not in step_metrics.window_metrics(
        0.0, _steps(times), 100
    )


def test_window_metrics_after_leaves_the_traced_part_out():
    times = [0.5 * (i + 1) for i in range(4)] + [2.0 + 0.1 * (i + 1) for i in range(20)]
    m = step_metrics.window_metrics(0.0, _steps(times), 10, 1, after=2.0)
    assert m["steps"] == 19
    assert m["tokens_per_s"] == pytest.approx(100.0)


def test_too_few_samples_give_no_tail():
    m = step_metrics.window_metrics(0.0, _steps([0.1 * (i + 1) for i in range(8)]), 10, 1)
    assert m["step_ms_p90"] is None and m["tokens_per_s"] == pytest.approx(100.0)


def test_losses_ok():
    good = step_metrics.losses_ok(_steps([0.1 * i for i in range(30)]))
    assert good["non_finite"] == 0 and good["falls"]
    bad = _steps([0.1 * i for i in range(30)])
    bad[3]["loss"] = float("nan")
    for s in bad[-10:]:
        s["loss"] = 9.0
    res = step_metrics.losses_ok(bad)
    assert res["non_finite"] == 1 and not res["falls"]


def test_records_of_a_killed_process_are_read_up_to_the_torn_line(tmp_path):
    p = tmp_path / "steps.jsonl"
    p.write_text(
        '{"window_open": 1.0, "step": 3, "pid": 7}\n'
        '{"step": 4, "t_done": 1.2, "loss": 5.0}\n'
        '{"window_open": 9.0, "step": 4, "pid": 8}\n'
        '{"step": 5, "t_done": 9.5, "loss": 4.9}\n'
        '{"step": 6, "t_do'
    )
    incs = step_metrics.split_incarnations(step_metrics.read_records(str(p)))
    assert [(i["pid"], len(i["steps"])) for i in incs] == [(7, 1), (8, 1)]
