"""The start-up timeline the program keeps itself (ISSUE 55): phase
marks in the process as well as in the file, one generation kept when
a writer starts its new set, JAX's own trace / lower / compile /
cache-load reports as records and spans by function, the trainer's
first dispatch and its pricing of the step, the launcher's three
steps; all returned by ``obs.profiling.startup_timeline()``.
"""

import functools
import json
import os
import random
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.obs import profiling
from dlrover_tpu.obs.timeline import reconstruct_recovery_timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracer():
    tr = obs.configure_tracer()
    yield tr
    obs.disable_tracer()


@pytest.fixture()
def clean_marks(monkeypatch):
    """No phases file and no marks of an earlier test."""
    monkeypatch.delenv("DLROVER_TPU_PHASES_FILE", raising=False)
    monkeypatch.setattr(profiling._TIMELINE, "marks", {})


@pytest.fixture()
def compile_cache(tmp_path):
    """A persistent compile cache of the test's own, every compile
    kept; the configuration as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _records_of(name):
    return [r for r in profiling.startup_timeline()["compile"]
            if name in r["fn"]]


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


# -- the marks ---------------------------------------------------------


def test_marks_are_kept_in_the_process_with_no_phases_file(
    clean_marks, tmp_path
):
    before = TrainingMonitor.phase_marks()
    assert before == {}
    TrainingMonitor.mark_phase("proc_start")
    TrainingMonitor.mark_phase("dist_ready")
    marks = TrainingMonitor.phase_marks()
    assert list(marks) == ["proc_start", "dist_ready"]
    assert marks["proc_start"] <= marks["dist_ready"]
    # A copy: the caller's edits are its own.
    marks.clear()
    assert set(TrainingMonitor.phase_marks()) == {"proc_start", "dist_ready"}
    timeline = profiling.startup_timeline()
    assert set(timeline) == {"marks", "compile"}
    assert timeline["marks"] == TrainingMonitor.phase_marks()
    assert not list(tmp_path.iterdir())  # and nothing was written


@pytest.mark.parametrize("starter,mine,others", [
    ("proc_start", ("dist_ready", "built"),
     ("agent.spawned", "prev.agent.spawned")),
    ("agent.exit_seen", ("agent.spawned", "agent.persist_done"),
     ("built", "prev.built")),
])
def test_a_new_set_moves_one_generation_under_prev_and_drops_the_one_before(
    clean_marks, tmp_path, starter, mine, others
):
    """``proc_start`` for the trainer's marks, ``agent.exit_seen`` for
    the agent's; a ``prev.`` key belongs to the writer of the name
    behind it, so the other writer's are left alone. File and dict
    hold the same."""
    path = str(tmp_path / "phases.json")

    def both():
        with open(path) as f:
            in_file = json.load(f)
        assert in_file == TrainingMonitor.phase_marks()
        return in_file

    for name in (starter,) + mine + others:
        TrainingMonitor.mark_phase(name, path)
    first = both()
    TrainingMonitor.mark_phase(starter, path)
    second = both()
    assert set(second) == (
        {starter} | {"prev." + k for k in (starter,) + mine} | set(others)
    )
    for name in (starter,) + mine:
        assert second["prev." + name] == first[name]
    assert second[starter] >= first[starter]
    for name in others:
        assert second[name] == first[name]
    # Once more: the generation before the last is gone.
    TrainingMonitor.mark_phase(mine[0], path)
    TrainingMonitor.mark_phase(starter, path)
    third = both()
    assert set(third) == (
        {starter, "prev." + starter, "prev." + mine[0]} | set(others)
    )
    assert third["prev." + starter] == second[starter]


def test_the_generation_moves_under_the_files_lock(clean_marks, tmp_path):
    """Two writers, each starting new sets over and over: no mark of
    the other is lost while a set moves."""
    path = str(tmp_path / "phases.json")
    code = (
        "import sys\n"
        "from dlrover_tpu.agent.monitor import TrainingMonitor\n"
        "starter, prefix, path = sys.argv[1:4]\n"
        "for i in range(40):\n"
        "    TrainingMonitor.mark_phase(starter, path)\n"
        "    TrainingMonitor.mark_phase(f'{prefix}m', path)\n"
    )
    procs = [
        subprocess.Popen([sys.executable, "-c", code, starter, prefix, path],
                         cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO))
        for starter, prefix in (("proc_start", ""), ("agent.exit_seen", "agent."))
    ]
    for p in procs:
        assert p.wait(timeout=120) == 0
    with open(path) as f:
        assert set(json.load(f)) == {
            "proc_start", "m", "prev.proc_start", "prev.m",
            "agent.exit_seen", "agent.m", "prev.agent.exit_seen",
            "prev.agent.m",
        }


# -- JAX's compile pipeline --------------------------------------------


def test_installing_the_listeners_twice_registers_them_once():
    from jax._src import monitoring

    assert profiling.install_compile_listeners()
    def counts():
        return [len(monitoring.get_event_time_span_listeners()),
                len(monitoring.get_event_duration_listeners()),
                len(monitoring.get_scalar_listeners())]

    before = counts()
    assert profiling.install_compile_listeners()
    profiling.CompileTracker("some_fn")
    assert counts() == before
    mine = profiling._TIMELINE
    assert monitoring.get_scalar_listeners().count(mine.on_scalar) == 1
    assert monitoring.get_event_time_span_listeners().count(mine.on_time_span) == 1
    assert monitoring.get_event_duration_listeners().count(mine.on_duration) == 1


def test_a_jitted_function_is_on_the_timeline_by_name_and_loads_from_the_cache(
    compile_cache,
):
    profiling.install_compile_listeners()

    @jax.jit
    def toy_inner_fn(x):
        return x * 3.0

    @jax.jit
    def toy_timeline_fn(x):
        return toy_inner_fn(x).sum()

    x = jnp.ones((8, 8))  # (its own small programs first)
    toy_timeline_fn(x).block_until_ready()
    cold = _records_of("toy_timeline_fn")
    assert [r["stage"] for r in cold] == ["trace", "lower", "backend_compile"]
    assert all(r["t0"] <= r["t1"] for r in cold)
    # Traced inside toy_timeline_fn's trace: part of it, no record.
    assert not _records_of("toy_inner_fn")
    assert [r["t0"] for r in cold] == sorted(r["t0"] for r in cold)
    # A dispatch the jit cache serves tells the listeners nothing.
    toy_timeline_fn(x).block_until_ready()
    assert len(_records_of("toy_timeline_fn")) == 3
    # What a second process would do: nothing in memory, the
    # executable on disk. JAX's backend-compile span is around the
    # look in the cache, so the load carries the function's name and
    # is no compile: a hit is a ``cache_load`` record, a miss a
    # ``backend_compile``.
    jax.clear_caches()
    toy_timeline_fn(x).block_until_ready()
    warm = _records_of("toy_timeline_fn")[3:]
    assert [r["stage"] for r in warm] == ["trace", "lower", "cache_load"]
    assert warm[-1]["t0"] <= warm[-1]["t1"]


def test_only_the_outermost_trace_is_a_record_and_a_span(tracer):
    """Traced inside another's trace, or by a lowering rule inside
    the lowering (a scan's index arithmetic): part of that stage. A
    step whose layers stand in line would otherwise fill the ring."""
    profiling.install_compile_listeners()

    def toy_scanned_fn(x):
        def body(c, _):
            return jnp.tanh(c) * 2, None

        y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=3)
        return jnp.sum(y)

    x = jnp.ones(4)
    # The records are a ring of the newest 4,096: once this process
    # has made that many, a position taken before is past its end.
    # JAX stamps a stage's start on ``time.time()``.
    started = time.time()
    n_events = len(tracer.events())
    jax.jit(jax.value_and_grad(toy_scanned_fn)).lower(x)
    new = [
        r for r in profiling.startup_timeline()["compile"]
        if r["t0"] >= started
    ]
    assert [(r["stage"], r["fn"]) for r in new if r["stage"] == "trace"] == [
        ("trace", "toy_scanned_fn")
    ]
    assert [r["stage"] for r in new].count("lower") == 1
    spans = [e["name"] for e in tracer.events()[n_events:]]
    assert spans.count("jax.trace") == 1 and spans.count("jax.lower") == 1


def test_a_trace_inside_a_trace_is_counted_once():
    recs = [{"t0": 0.0, "t1": 2.0}, {"t0": 0.5, "t1": 1.5},
            {"t0": 3.0, "t1": 4.0}, {"t0": 3.5, "t1": 4.5}]
    assert profiling.union_seconds(recs) == pytest.approx(3.5)
    assert profiling.union_seconds([]) == 0.0


def test_each_record_is_a_span_stamped_with_its_own_start_and_a_counter(
    tracer,
):
    profiling.install_compile_listeners()
    seconds = obs.get_registry().get("dlrover_compile_stage_seconds_total")
    before = seconds.value(stage="trace")

    # A constant no earlier run drew: where another test of the worker
    # has turned the persistent cache on, a body that cache has seen
    # is answered by a ``jax.cache_load`` in the compile's place.
    drawn = random.SystemRandom().random()

    @jax.jit
    def toy_span_fn(x):
        return x + drawn

    toy_span_fn(jnp.ones(4)).block_until_ready()
    ev = _by_name(e for e in tracer.events() if "toy_span_fn" in e.get("fn", ""))
    assert set(ev) == {"jax.trace", "jax.lower", "jax.backend_compile"}
    recs = {r["stage"]: r for r in _records_of("toy_span_fn")}
    for stage, rec in recs.items():
        (span,) = ev["jax." + stage]
        assert span["ts"] == rec["t0"]
        assert span["dur_s"] == pytest.approx(rec["t1"] - rec["t0"], abs=1e-5)
    assert seconds.value(stage="trace") >= before + (
        recs["trace"]["t1"] - recs["trace"]["t0"]
    )
    text = obs.get_registry().render()
    assert 'dlrover_compile_stage_seconds_total{stage="lower"}' in text


def test_with_the_tracer_off_it_stays_off():
    obs.disable_tracer()
    profiling.install_compile_listeners()

    @jax.jit
    def toy_quiet_fn(x):
        return x - 1

    toy_quiet_fn(jnp.ones(4)).block_until_ready()
    TrainingMonitor.mark_phase("devices_ready")
    assert _records_of("toy_quiet_fn")
    assert obs.get_tracer() is None
    assert obs.completed_span("jax.trace", 1.0, 2.0, fn="f") is None


# -- the trainer's first step ------------------------------------------


def _toy_trainer():
    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.models import gpt
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    cfg = gpt.GPTConfig(vocab_size=64, block_size=16, n_layer=1,
                        n_head=2, n_embd=16)
    loss = functools.partial(gpt.loss_fn, cfg=cfg)
    sample = jnp.zeros((2, 16), jnp.int32)
    res = auto_accelerate(
        functools.partial(gpt.init_params, cfg=cfg), loss,
        gpt.param_logical_axes(cfg), (sample, sample),
        strategy=Strategy(mesh_shape=(("data", 1),), optimizer="adamw",
                          micro_batch_size=2),
        devices=jax.devices()[:1],
    )
    params, opt_state = res.init_fn(jax.random.PRNGKey(0))
    trainer = ElasticTrainer(res.mesh, loss, res.optimizer,
                             global_batch_size=2, micro_batch_size=2)
    return trainer, params, opt_state


def test_the_first_step_places_first_dispatch_prices_the_step_and_tags_the_compile(
    tracer, clean_marks,
):
    trainer, params, opt_state = _toy_trainer()
    assert "first_dispatch" not in TrainingMonitor.phase_marks()
    tok = np.zeros((2, 16), np.int32)
    n_price = len([r for r in profiling.startup_timeline()["compile"]
                   if r["stage"] == "price"])
    params, opt_state, _ = trainer.train_step(params, opt_state, tok, tok)
    marks = TrainingMonitor.phase_marks()
    assert marks["devices_ready"] <= marks["accelerate_done"] <= marks["first_dispatch"]
    timeline = profiling.startup_timeline()
    price = [r for r in timeline["compile"] if r["stage"] == "price"][n_price:]
    assert [r["fn"] for r in price] == ["train_step"]
    assert marks["first_dispatch"] <= price[0]["t0"] <= price[0]["t1"]
    # The pricing traces and lowers the step: its records lie inside.
    inside = [r for r in timeline["compile"]
              if "train_step" in r["fn"] and r["stage"] in ("trace", "lower")
              and price[0]["t0"] <= r["t0"] and r["t1"] <= price[0]["t1"]]
    assert {r["stage"] for r in inside} == {"trace", "lower"}
    ev = _by_name(tracer.events())
    (span,) = ev["trainer.price_step"]
    assert span["flops"] > 0 and span["dur_s"] >= 0
    (first,) = ev["trainer.first_dispatch"]
    assert first["ts"] <= span["ts"]
    (compile_event,) = ev["trainer.compile"]
    assert compile_event["fn"] == "train_step" and compile_event["total"] == 1
    for tag in ("trace_s", "lower_s", "backend_compile_s", "cache_load_s"):
        assert compile_event[tag] >= 0
    assert compile_event["cache_hit"] is (compile_event["cache_load_s"] > 0)
    assert (compile_event["backend_compile_s"]
            + compile_event["cache_load_s"]) > 0
    assert compile_event["backend_compile_s"] <= compile_event["dur_s"] + 0.01
    # A later step adds nothing: no mark, no pricing, no compile.
    mark = marks["first_dispatch"]
    n_events = len(tracer.events())
    trainer.train_step(params, opt_state, tok, tok)
    assert TrainingMonitor.phase_marks()["first_dispatch"] == mark
    later = _by_name(tracer.events()[n_events:])
    assert set(later) == {"trainer.dispatch"}
    assert len([r for r in profiling.startup_timeline()["compile"]
                if r["stage"] == "price"]) == n_price + 1


# -- the launcher's three steps ----------------------------------------


@pytest.mark.parametrize("nproc,master,want", [
    (0, "", ["agent.launch_start", "agent.chips_counted",
             "agent.master_ready"]),
    (1, "", ["agent.launch_start", "agent.master_ready"]),
    (0, "127.0.0.1:1", ["agent.launch_start", "agent.chips_counted"]),
])
def test_the_launcher_marks_its_steps_only_where_they_ran(
    clean_marks, monkeypatch, nproc, master, want
):
    from dlrover_tpu.trainer import elastic_run

    class FakeAgent:
        def __init__(self, config, entry_cmd):
            self.marks_at_start = list(TrainingMonitor.phase_marks())

        def run(self):
            FakeAgent.seen = self.marks_at_start
            return 0

        def stop(self):
            pass

    master_proc = types.SimpleNamespace(
        terminate=lambda: None, wait=lambda timeout=None: 0, kill=lambda: None
    )
    monkeypatch.setattr(elastic_run, "ElasticAgent", FakeAgent)
    monkeypatch.setattr(elastic_run, "_local_chip_count", lambda: 1)
    monkeypatch.setattr(elastic_run, "_launch_local_master",
                        lambda *a: (master_proc, "127.0.0.1:2"))
    monkeypatch.setattr(obs, "install_flight_recorder", lambda *a, **k: None)
    # (run() writes these into the environment: put back afterwards.)
    for name in ("DLROVER_TPU_MASTER_ADDR", "DLROVER_TPU_NODE_ID",
                 "DLROVER_TPU_ROLE"):
        monkeypatch.setenv(name, "")
    monkeypatch.setenv("DLROVER_TPU_NODE_RANK", "0")
    args = elastic_run.parse_args(
        ["--standalone", "--nproc_per_node", str(nproc)]
        + (["--master", master] if master else []) + ["train.py"]
    )
    assert elastic_run.run(args) == 0
    assert FakeAgent.seen == want
    marks = TrainingMonitor.phase_marks()
    assert [marks[k] for k in want] == sorted(marks[k] for k in want)


# -- what reads the event stream stays what it was ---------------------


def test_the_recovery_breakdown_ignores_first_dispatch_and_the_jax_spans():
    t = 1000.0
    events = [
        {"name": "node.fail", "ts": t},
        {"name": "trainer.proc_start", "ts": t + 4.0},
        {"name": "trainer.dist_ready", "ts": t + 9.0},
        {"name": "trainer.built", "ts": t + 15.0},
        {"name": "trainer.restore_done", "ts": t + 18.0},
        {"name": "trainer.first_step_done", "ts": t + 40.0},
    ]
    new = [
        {"name": "jax.trace", "ts": t + 19.0, "dur_s": 3.0, "fn": "train_step"},
        {"name": "trainer.first_dispatch", "ts": t + 18.5},
        {"name": "trainer.price_step", "ts": t + 18.6, "dur_s": 4.0},
        {"name": "jax.backend_compile", "ts": t + 25.0, "dur_s": 12.0,
         "fn": "jit_train_step"},
    ]
    plain = reconstruct_recovery_timeline(events)
    mixed = reconstruct_recovery_timeline(
        sorted(events + new, key=lambda e: e["ts"])
    )
    assert mixed.to_dict() == plain.to_dict()
    assert plain.complete and plain.phases["first-step"] == pytest.approx(22.0)
    from dlrover_tpu.obs.goodput import attribute_goodput

    assert (attribute_goodput(sorted(events + new, key=lambda e: e["ts"])).to_dict()
            == attribute_goodput(events).to_dict())


def test_obs_report_prints_a_recompiles_stage_seconds():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    old = {"name": "trainer.compile", "ts": 1.0, "fn": "train_step",
           "dur_s": 9.0, "total": 1}
    new = dict(old, ts=2.0, total=2, dur_s=1.5, trace_s=0.7, lower_s=0.5,
               backend_compile_s=0.0, cache_load_s=0.2, cache_hit=True)
    text = obs_report.perf_summary([old, new])
    assert "train_step x2 (10.50s)" in text
    assert ("train_step #2: 1.50s = trace 0.70 + lower 0.50 + "
            "backend_compile 0.00 + cache_load 0.20 (cache hit)") in text
    assert "#1" not in obs_report.perf_summary([old])
