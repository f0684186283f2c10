"""The program's own spans, marks and kernel names (ISSUE 24): a span
reaches the profiler's trace as well as the JSON lines, the checkpoint
path says from inside what a save and a restore are made of, the
agent's saver what a persist is made of, the phase marks hold two
writers, and every Pallas kernel under ``ops/`` has a name a device
trace can follow.
"""

import ast
import glob
import json
import os
import subprocess
import sys
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu import obs
from dlrover_tpu.agent.monitor import TrainingMonitor
from dlrover_tpu.obs.tracer import PROFILER_PREFIX

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(REPO, "dlrover_tpu", "ops")


@pytest.fixture()
def tracer():
    tr = obs.configure_tracer()
    yield tr
    obs.disable_tracer()


def _profile_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROFILER_PREFIX):
                    out.append((plane.name, ev, dict(ev.stats)))
    return out


def test_a_span_lands_in_the_profiler_with_the_jsonl_sink_off(tmp_path):
    obs.disable_tracer()
    assert not obs.tracing_enabled()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("ckpt.save_memory", step=7) as span:
            with obs.span("ckpt.d2h", bytes=12):
                jnp.ones(4).block_until_ready()
            span.set(ok=True)
    got = {ev.name: (plane, ev, stats)
           for plane, ev, stats in _profile_events(str(tmp_path))}
    plane, outer, stats = got["dlrover.ckpt.save_memory"]
    assert plane.startswith("/host:CPU")
    assert stats["step"] == 7 and stats["ok"] == 1
    _, inner, inner_stats = got["dlrover.ckpt.d2h"]
    assert inner_stats["bytes"] == 12
    # One clock: the child lies inside its parent.
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)


def test_a_process_that_never_imported_jax_imports_none_through_obs():
    code = (
        "import sys\n"
        "from dlrover_tpu import obs\n"
        "import dlrover_tpu.agent.agent, dlrover_tpu.agent.ckpt_saver\n"
        "obs.configure_tracer()\n"
        "with obs.span('ckpt.persist', step=1) as s:\n"
        "    s.set(bytes=2)\n"
        "obs.event('agent.worker_spawned', worker_pid=1)\n"
        "ev = obs.get_tracer().events()\n"
        "assert ev[0]['name'] == 'ckpt.persist' and ev[0]['bytes'] == 2\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


# -- checkpoint: a save, a persist and a restore from inside -----------


@pytest.fixture()
def checkpointer(tmp_path, monkeypatch):
    from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
        Checkpointer,
    )

    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", f"t{uuid.uuid4().hex[:8]}")
    monkeypatch.delenv("DLROVER_TPU_AGENT_PRESENT", raising=False)
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    yield ckpt
    saver = ckpt._self_hosted_saver
    ckpt.close()
    for shm in saver._shms:
        shm.unlink()


def _state():
    return {
        "w": jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64),
        "b": jnp.ones((64,), jnp.bfloat16),
    }


def _state_bytes(state):
    return sum(x.nbytes for x in jax.tree.leaves(state))


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def _inside(child, parent):
    return (
        parent["mono"] <= child["mono"]
        and child["mono"] + child["dur_s"]
        <= parent["mono"] + parent["dur_s"] + 1e-5
    )


@pytest.fixture()
def saved_and_restored(tracer, checkpointer):
    """One save to disk, its persist, one restore: the events."""
    from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
        StorageType,
    )

    state = _state()
    assert checkpointer.save_checkpoint(3, state, StorageType.DISK)
    assert checkpointer.wait_latest_checkpoint(timeout=30)
    shardings = jax.tree.map(lambda x: x.sharding, state)
    restored = checkpointer.load_checkpoint(state, shardings=shardings)
    np.testing.assert_array_equal(restored["w"], state["w"])
    return state, _by_name(tracer.events())


def test_a_save_says_what_it_is_made_of(saved_and_restored):
    state, ev = saved_and_restored
    (save,), (mem,) = ev["ckpt.save"], ev["ckpt.save_memory"]
    (d2h,), (copy,) = ev["ckpt.d2h"], ev["ckpt.shm_copy"]
    (notify,) = ev["ckpt.notify_agent"]
    assert save["step"] == 3 and save["storage"] == "disk" and save["ok"]
    assert "parent" not in save
    assert mem["parent"] == "ckpt.save" and notify["parent"] == "ckpt.save"
    # The write into the segment follows the arrivals: the copy runs
    # inside the read, which ends once the last byte is in the segment.
    assert d2h["parent"] == "ckpt.save_memory"
    assert copy["parent"] == "ckpt.d2h"
    assert d2h["bytes"] == copy["bytes"] == _state_bytes(state)
    assert d2h["leaves"] == 2
    # Every planned shard's transfer was started before the first wait
    # (two small leaves on one device: two shards, nothing cut).
    assert d2h["in_flight"] == 2
    assert d2h["gbps"] > 0
    assert _inside(mem, save) and _inside(notify, save)
    assert _inside(d2h, mem) and _inside(copy, d2h)
    # Of the copy's span, the seconds spent writing; the rest waited.
    assert 0 <= copy["write_s"] <= copy["dur_s"] + 1e-5
    assert mem["mono"] + mem["dur_s"] <= notify["mono"] + 1e-5


def test_a_restore_says_what_it_is_made_of(saved_and_restored):
    state, ev = saved_and_restored
    (restore,) = ev["ckpt.restore"]
    (read,), (put,) = ev["ckpt.restore_read"], ev["ckpt.restore_put"]
    assert read["parent"] == put["parent"] == "ckpt.restore"
    assert read["bytes"] == _state_bytes(state)
    assert read["source"] == "disk" and read["step"] == 3
    assert _inside(read, restore) and _inside(put, restore)
    assert read["mono"] + read["dur_s"] <= put["mono"] + 1e-5
    # The mark between the two halves, mirrored into the tracer.
    (mark,) = ev["trainer.restore_read_done"]
    assert read["mono"] + read["dur_s"] <= mark["mono"] + 1e-5 <= (
        put["mono"] + 2e-5
    )


def test_the_saver_says_what_a_persist_is_made_of(saved_and_restored):
    state, ev = saved_and_restored
    (persist,) = ev["ckpt.persist"]
    assert persist["step"] == 3 and persist["shards"] == 1
    # The payload is laid out with 128-byte alignment between leaves.
    assert 0 <= persist["bytes"] - _state_bytes(state) < 128 * 2
    children = ("ckpt.persist_snapshot", "ckpt.persist_write",
                "ckpt.persist_commit")
    last_end = persist["mono"]
    for name in children:
        (child,) = ev[name]
        assert child["parent"] == "ckpt.persist"
        assert _inside(child, persist)
        assert child["mono"] + 1e-5 >= last_end
        last_end = child["mono"] + child["dur_s"]


def test_a_flush_with_nothing_staged_says_so(tracer, tmp_path, monkeypatch):
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", f"t{uuid.uuid4().hex[:8]}")
    saver = AsyncCheckpointSaver(checkpoint_dir=str(tmp_path / "c"))
    try:
        assert saver.save_shm_to_storage() is False
    finally:
        saver.close()
    (flush,) = _by_name(tracer.events())["ckpt.flush_on_restart"]
    assert flush["found"] is False


def test_a_dropped_save_is_an_event(tracer, checkpointer):
    from dlrover_tpu.trainer.flash_checkpoint.checkpointer import (
        StorageType,
    )

    lock = checkpointer.engine._lock
    assert lock.acquire(blocking=False)  # the agent persisting
    try:
        assert not checkpointer.save_checkpoint(
            5, _state(), StorageType.MEMORY
        )
    finally:
        lock.release()
    ev = _by_name(tracer.events())
    (skipped,) = ev["ckpt.save_skipped"]
    assert skipped["step"] == 5 and skipped["reason"] == "shm_busy"
    (save,) = ev["ckpt.save"]
    assert save["ok"] is False and save["storage"] == "memory"
    assert "ckpt.save_memory" not in ev


# -- the phase marks: two writers, one file ----------------------------


def _marks(path):
    with open(path) as f:
        return json.load(f)


def test_proc_start_keeps_the_agents_marks_and_drops_the_rest(tmp_path):
    path = str(tmp_path / "phases.json")
    for name in ("proc_start", "built", "agent.exit_seen", "agent.spawned"):
        TrainingMonitor.mark_phase(name, path)
    assert set(_marks(path)) == {
        "proc_start", "built", "agent.exit_seen", "agent.spawned"}
    TrainingMonitor.mark_phase("proc_start", path)
    # (What a writer empties stands one generation under ``prev.``.)
    assert set(_marks(path)) == {
        "proc_start", "agent.exit_seen", "agent.spawned",
        "prev.proc_start", "prev.built"}
    TrainingMonitor.mark_phase("dist_ready", path)
    # The next failure: the agent's marks of the last relaunch go, the
    # dead trainer's stay until its successor starts.
    TrainingMonitor.mark_phase("agent.exit_seen", path)
    assert set(_marks(path)) == {
        "proc_start", "dist_ready", "agent.exit_seen",
        "prev.proc_start", "prev.built",
        "prev.agent.exit_seen", "prev.agent.spawned"}


def test_the_agents_marks_are_not_mirrored_as_trainer_events(tracer):
    TrainingMonitor.mark_phase("agent.spawned")
    TrainingMonitor.mark_phase("devices_ready")
    assert [e["name"] for e in tracer.events()] == ["trainer.devices_ready"]


def test_two_writing_processes_lose_no_mark(tmp_path):
    path = str(tmp_path / "phases.json")
    n = 60
    code = (
        "import sys\n"
        "from dlrover_tpu.agent.monitor import TrainingMonitor\n"
        "prefix, path, n = sys.argv[1], sys.argv[2], int(sys.argv[3])\n"
        "for i in range(n):\n"
        "    TrainingMonitor.mark_phase(f'{prefix}{i}', path)\n"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, prefix, path, str(n)], cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO),
        )
        for prefix in ("agent.m", "t")
    ]
    for p in procs:
        assert p.wait(timeout=120) == 0
    got = _marks(path)
    want = {f"{p}{i}" for p in ("agent.m", "t") for i in range(n)}
    assert set(got) == want


# -- the bootstrap and the step ----------------------------------------


def test_auto_accelerate_and_the_trainer_step_are_spanned(tracer, tmp_path):
    import functools

    from dlrover_tpu.accelerate import Strategy, auto_accelerate
    from dlrover_tpu.models import gpt
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    path = str(tmp_path / "phases.json")
    os.environ["DLROVER_TPU_PHASES_FILE"] = path
    try:
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
    finally:
        del os.environ["DLROVER_TPU_PHASES_FILE"]
    marks = _marks(path)
    assert marks["devices_ready"] <= marks["accelerate_done"]
    trainer = ElasticTrainer(res.mesh, loss, res.optimizer,
                             global_batch_size=2, micro_batch_size=2)
    tok = np.zeros((2, 16), np.int32)
    for _ in range(2):
        params, opt_state, _ = trainer.train_step(params, opt_state, tok, tok)
    ev = _by_name(tracer.events())
    assert ev["accel.build"][0]["strategy"]
    assert len(ev["accel.init_state"]) == 1
    first, second = ev["trainer.dispatch"]
    assert (first["step"], first["compiled"]) == (1, True)
    assert (second["step"], second["compiled"]) == (2, False)
    assert len(ev["trainer.compile_done"]) == 1


# -- kernel names -------------------------------------------------------


def _pallas_calls():
    """(file, line, has a name=) of every ``pl.pallas_call`` under ops/."""
    out = []
    for path in sorted(glob.glob(os.path.join(OPS, "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            called = fn.attr if isinstance(fn, ast.Attribute) else getattr(
                fn, "id", "")
            if called == "pallas_call":
                out.append((
                    os.path.basename(path), node.lineno,
                    any(k.arg == "name" for k in node.keywords),
                ))
    return out


def test_every_pallas_call_under_ops_has_a_name():
    calls = _pallas_calls()
    assert len(calls) >= 6
    assert [c for c in calls if not c[2]] == []


def test_the_flash_kernels_are_named_in_the_jaxpr():
    from dlrover_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)

    def loss(q):
        return flash_attention(q, q, q, interpret=True).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(q))
    assert "name=flash_attention_fwd" in text
    assert "name=flash_attention_bwd" in text
