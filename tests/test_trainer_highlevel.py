"""High-level Trainer, hang detector, paral-config tuner."""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accelerate import Strategy
from dlrover_tpu.agent.hang_detector import HangDetector
from dlrover_tpu.agent.paral_config_tuner import (
    ParalConfigTuner,
    read_parallel_config,
)
from dlrover_tpu.common import messages as msg
from dlrover_tpu.models import gpt
from dlrover_tpu.trainer.trainer import Trainer, TrainingArguments


@pytest.fixture(autouse=True)
def _own_job_name(monkeypatch):
    """The checkpoint lock/shm sockets live under /tmp keyed by job
    name; with the default name another xdist worker's suite can
    remove this one's lock server mid-test (seen in 2 of 4 full runs,
    PR 21)."""
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", f"highlevel{os.getpid()}")


CFG = gpt.GPTConfig(
    vocab_size=128, block_size=32, n_layer=2, n_head=2, n_embd=32,
    dtype=jnp.float32, remat=False,
)


class TokenDataset:
    def __init__(self, n=256, seed=0):
        rng = np.random.default_rng(seed)
        self.data = rng.integers(
            0, CFG.vocab_size, size=(n, CFG.block_size + 1)
        ).astype(np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return self.data[i, :-1], self.data[i, 1:]


def test_trainer_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "DLROVER_TPU_METRICS_FILE", str(tmp_path / "metrics.json")
    )
    args = TrainingArguments(
        max_steps=6,
        global_batch_size=16,
        micro_batch_size=4,
        checkpoint_dir=str(tmp_path / "ckpt"),
        save_steps=3,
        log_steps=2,
        strategy=Strategy(
            mesh_shape=(("data", 4),), dtype="float32",
            micro_batch_size=4,
        ),
    )
    t = Trainer(
        functools.partial(gpt.init_params, cfg=CFG),
        functools.partial(gpt.loss_fn, cfg=CFG),
        gpt.param_logical_axes(CFG),
        TokenDataset(),
        args,
    )
    out = t.train()
    assert out["final_step"] == 6
    assert out["final_loss"] is not None
    # metrics file written for the agent's training monitor
    with open(tmp_path / "metrics.json") as f:
        assert json.load(f)["step"] == 6

    # resume: a fresh Trainer continues from the checkpoint
    args2 = TrainingArguments(**{
        **args.__dict__, "max_steps": 8,
    })
    t2 = Trainer(
        functools.partial(gpt.init_params, cfg=CFG),
        functools.partial(gpt.loss_fn, cfg=CFG),
        gpt.param_logical_axes(CFG),
        TokenDataset(),
        args2,
    )
    out2 = t2.train()
    assert out2["final_step"] == 8


def test_trainer_periodic_and_standalone_eval(tmp_path, monkeypatch):
    """Evaluator role: periodic eval during train() and a standalone
    evaluate() that restores the latest committed checkpoint."""
    monkeypatch.setenv(
        "DLROVER_TPU_METRICS_FILE", str(tmp_path / "m.json")
    )
    args = TrainingArguments(
        max_steps=4,
        global_batch_size=8,
        micro_batch_size=4,
        checkpoint_dir=str(tmp_path / "ckpt_eval"),
        save_steps=4,
        eval_steps=2,
        eval_max_batches=2,
        strategy=Strategy(
            mesh_shape=(("data", 4),), dtype="float32",
            micro_batch_size=4,
        ),
    )
    t = Trainer(
        functools.partial(gpt.init_params, cfg=CFG),
        functools.partial(gpt.loss_fn, cfg=CFG),
        gpt.param_logical_axes(CFG),
        TokenDataset(),
        args,
        eval_dataset=TokenDataset(n=64, seed=9),
    )
    out = t.train()
    assert out["eval"] is not None
    assert np.isfinite(out["eval"]["eval_loss"])
    assert out["eval"]["perplexity"] > 1.0

    # standalone evaluator node: fresh Trainer, params from checkpoint
    t2 = Trainer(
        functools.partial(gpt.init_params, cfg=CFG),
        functools.partial(gpt.loss_fn, cfg=CFG),
        gpt.param_logical_axes(CFG),
        TokenDataset(),
        args,
        eval_dataset=TokenDataset(n=64, seed=9),
    )
    metrics = t2.evaluate()
    np.testing.assert_allclose(
        metrics["eval_loss"], out["eval"]["eval_loss"], rtol=1e-5
    )


def test_trainer_with_llama_family(tmp_path, monkeypatch):
    """The high-level Trainer is model-agnostic: drive it with the
    Llama family (RoPE/GQA/SwiGLU) end to end, including a save."""
    from dlrover_tpu.models import llama

    lcfg = llama.LlamaConfig.tiny()

    class LlamaData:
        def __init__(self, n=128, seed=1):
            rng = np.random.default_rng(seed)
            self.data = rng.integers(
                0, lcfg.vocab_size, size=(n, lcfg.block_size + 1)
            ).astype(np.int32)

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return self.data[i, :-1], self.data[i, 1:]

    monkeypatch.setenv(
        "DLROVER_TPU_METRICS_FILE", str(tmp_path / "m.json")
    )
    args = TrainingArguments(
        max_steps=4,
        global_batch_size=8,
        micro_batch_size=4,
        checkpoint_dir=str(tmp_path / "ckpt_llama"),
        save_steps=4,
        strategy=Strategy(
            mesh_shape=(("data", 2), ("fsdp", 2), ("tensor", 2)),
            dtype="float32",
            micro_batch_size=4,
        ),
    )
    t = Trainer(
        functools.partial(llama.init_params, cfg=lcfg),
        functools.partial(llama.loss_fn, cfg=lcfg),
        llama.param_logical_axes(lcfg),
        LlamaData(),
        args,
    )
    out = t.train()
    assert out["final_step"] == 4
    assert np.isfinite(out["final_loss"])


def test_trainer_cosine_schedule_and_clipping(tmp_path, monkeypatch):
    """Warmup-cosine LR + grad clipping train end to end, and the
    standalone evaluator rebuilds the schedule-bearing opt skeleton."""
    monkeypatch.setenv(
        "DLROVER_TPU_METRICS_FILE", str(tmp_path / "m.json")
    )
    args = TrainingArguments(
        max_steps=4,
        global_batch_size=8,
        micro_batch_size=4,
        checkpoint_dir=str(tmp_path / "ckpt_sched"),
        save_steps=4,
        warmup_steps=2,
        lr_schedule="cosine",
        grad_clip_norm=1.0,
        strategy=Strategy(
            mesh_shape=(("data", 4),), dtype="float32",
            micro_batch_size=4,
        ),
    )

    def build():
        return Trainer(
            functools.partial(gpt.init_params, cfg=CFG),
            functools.partial(gpt.loss_fn, cfg=CFG),
            gpt.param_logical_axes(CFG),
            TokenDataset(),
            args,
            eval_dataset=TokenDataset(n=64, seed=3),
        )

    out = build().train()
    assert out["final_step"] == 4
    assert np.isfinite(out["final_loss"])
    # skeleton roundtrip: schedule state must match the checkpoint
    metrics = build().evaluate()
    assert np.isfinite(metrics["eval_loss"])


def test_make_optimizer_schedule_variants():
    from dlrover_tpu.accelerate import make_optimizer

    p = {"w": jnp.ones((8,))}
    for kw in (
        {"schedule": "constant"},
        {"schedule": "constant", "warmup_steps": 3},
        {"schedule": "cosine", "warmup_steps": 2, "decay_steps": 10},
        {"schedule": "cosine", "decay_steps": 10,
         "grad_clip_norm": 0.5},
    ):
        opt = make_optimizer("adamw", 1e-2, **kw)
        s = opt.init(p)
        g = {"w": jnp.full((8,), 10.0)}  # large grad: clipping binds
        u, s = opt.update(g, s, p)
        assert np.isfinite(float(jnp.sum(u["w"])))
    with pytest.raises(ValueError):
        make_optimizer("adamw", 1e-2, schedule="cosine")
    with pytest.raises(ValueError):
        make_optimizer("adamw", 1e-2, schedule="nope")
    with pytest.raises(ValueError):
        make_optimizer("adamw", 1e-2, grad_clip_norm=-1.0)
    # clipping actually binds: with sgd, ||update|| == lr * clip_norm
    # for a gradient far above the threshold
    lr, clip = 0.1, 0.5
    opt = make_optimizer("sgd", lr, grad_clip_norm=clip)
    p = {"w": jnp.zeros((4,))}
    s = opt.init(p)
    g = {"w": jnp.full((4,), 100.0)}
    u, _ = opt.update(g, s, p)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(u["w"])), lr * clip, rtol=1e-5
    )


def test_hang_detector_startup_grace_and_progress(tmp_path):
    path = str(tmp_path / "m.json")
    det = HangDetector(
        hang_timeout=0.2, startup_grace=0.3, metrics_file=path
    )
    assert not det.check()  # within startup grace
    time.sleep(0.35)
    assert det.check()  # no step ever landed
    det.reset()
    with open(path, "w") as f:
        json.dump({"step": 1}, f)
    assert not det.check()  # progress
    time.sleep(0.25)
    assert det.check()  # stalled past hang_timeout
    with open(path, "w") as f:
        json.dump({"step": 2}, f)
    assert not det.check()  # recovered


def test_paral_config_tuner_stages_file(tmp_path):
    class FakeClient:
        def __init__(self):
            self.cfg = msg.ParallelConfig(
                micro_batch_size=8, version=1
            )

        def get_parallel_config(self):
            return self.cfg

    path = str(tmp_path / "paral.json")
    client = FakeClient()
    tuner = ParalConfigTuner(client, config_file=path, interval=999)
    assert tuner.poll_once()
    staged = read_parallel_config(path)
    assert staged["micro_batch_size"] == 8
    # same version: no rewrite
    assert not tuner.poll_once()
    client.cfg = msg.ParallelConfig(micro_batch_size=16, version=2)
    assert tuner.poll_once()
    assert read_parallel_config(path)["micro_batch_size"] == 16


def test_trainer_applies_paral_config(tmp_path, monkeypatch):
    path = str(tmp_path / "paral.json")
    with open(path, "w") as f:
        json.dump({"micro_batch_size": 2, "version": 3}, f)
    monkeypatch.setenv("DLROVER_TPU_PARAL_CONFIG_FILE", path)

    def make():
        return Trainer(
            functools.partial(gpt.init_params, cfg=CFG),
            functools.partial(gpt.loss_fn, cfg=CFG),
            gpt.param_logical_axes(CFG),
            TokenDataset(),
            TrainingArguments(micro_batch_size=4),
        )

    # standalone (no agent): the file must be ignored
    monkeypatch.delenv("DLROVER_TPU_AGENT_PRESENT", raising=False)
    assert make().args.micro_batch_size == 4
    # under the agent: applied
    monkeypatch.setenv("DLROVER_TPU_AGENT_PRESENT", "1")
    assert make().args.micro_batch_size == 2


def test_servicer_parallel_config_roundtrip():
    from dlrover_tpu.master.master import JobMaster

    master = JobMaster(node_num=1)
    master.prepare()
    try:
        master.servicer.set_parallel_config(
            msg.ParallelConfig(micro_batch_size=16)
        )
        from dlrover_tpu.common.comm import RpcClient

        client = RpcClient(master.addr)
        cfg = client.get(msg.ParallelConfigRequest(node_id=0))
        assert cfg.micro_batch_size == 16
        assert cfg.version == 1
    finally:
        master.stop()
