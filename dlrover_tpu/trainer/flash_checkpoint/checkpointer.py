"""High-level Checkpointer frontend.

Parity with the reference's Checkpointer/StorageType
(dlrover/trainer/torch/flash_checkpoint/checkpointer.py:18,23) and its
per-framework subclasses (ddp.py, fsdp_engine.py, deepspeed.py,
megatron.py). In JAX one frontend covers DDP/FSDP/3D cases alike:
state is a single sharded pytree regardless of the parallelism
strategy, so there is nothing framework-specific to adapt — the engine
stages whatever shards this process owns.

When no host agent is present (standalone runs, notebooks), the
Checkpointer self-hosts an AsyncCheckpointSaver thread in-process, the
analogue of dlrover-run's local-master fallback.
"""

from __future__ import annotations

import os
from enum import Enum
from typing import Optional

from dlrover_tpu import obs
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.trainer.flash_checkpoint.engine import CheckpointEngine

logger = get_logger("flash_ckpt")

AGENT_ENV = "DLROVER_TPU_AGENT_PRESENT"


class StorageType(Enum):
    MEMORY = 0
    DISK = 1


class Checkpointer:
    def __init__(
        self,
        checkpoint_dir: str,
        local_rank: int = 0,
        save_timeout: float = 600.0,
    ):
        import jax

        self.checkpoint_dir = checkpoint_dir
        self._self_hosted_saver = None
        if os.getenv(AGENT_ENV, "") != "1":
            if local_rank != 0:
                # Standalone means this process is the only local
                # shard; a nonzero local_rank would point the engine at
                # a shm segment/lock the self-hosted saver never serves.
                logger.warning(
                    "standalone Checkpointer forces local_rank 0 "
                    "(got %s)", local_rank)
                local_rank = 0
            # Standalone: host the async saver ourselves. Note imports
            # stay inside so agent-managed trainers never pull it in.
            from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

            self._self_hosted_saver = (
                AsyncCheckpointSaver.start_async_saving_ckpt(
                    checkpoint_dir=checkpoint_dir,
                    local_shard_num=1,
                    global_shard_num=jax.process_count(),
                    is_commit_owner=jax.process_index() == 0,
                    commit_timeout=save_timeout,
                )
            )
        self.engine = CheckpointEngine(
            checkpoint_dir, local_rank=local_rank
        )
        # Step of the checkpoint most recently restored by
        # load_checkpoint (-1 = none restored yet), and the extras
        # saved alongside it (sampler state, user metadata).
        self.last_restored_step = -1
        self.last_restored_extra: dict = {}

    def save_checkpoint(
        self,
        step: int,
        state,
        storage_type: StorageType = StorageType.DISK,
        extra: Optional[dict] = None,
    ) -> bool:
        """Stage ``state`` (sharded jax pytree) into host shm; for
        DISK also trigger async persistence. Returns once staging is
        done — storage IO never blocks the train loop."""
        to_memory = storage_type == StorageType.MEMORY
        with obs.span(
            "ckpt.save", step=step,
            storage="memory" if to_memory else "disk",
        ) as span:
            save = (self.engine.save_to_memory if to_memory
                    else self.engine.save_to_storage)
            ok = save(step, state, extra)
            span.set(ok=ok)
            return ok

    def load_checkpoint(self, like, shardings=None,
                        step: Optional[int] = None):
        """Restore a committed checkpoint (the latest, or ``step=``),
        resharded onto the current mesh via ``shardings``. Returns the
        restored state pytree (shaped like ``like``), or None if no
        checkpoint; the step actually restored is in
        ``last_restored_step`` (NOT latest_step(), which may be newer
        when rolling back with step=)."""
        res = self.engine.load(like, shardings=shardings, step=step)
        if res is None:
            return None
        found_step, state, extra = res
        self.last_restored_step = found_step
        self.last_restored_extra = extra
        return state

    def latest_step(self) -> int:
        return self.engine.latest_step()

    def wait_latest_checkpoint(self, timeout: float = 60.0) -> bool:
        """Block until the most recently staged step is committed."""
        step = self.engine._cached_step
        if step < 0:
            return True
        return self.engine.wait_persisted(step, timeout)

    def close(self) -> None:
        self.engine.close()
        if self._self_hosted_saver is not None:
            self._self_hosted_saver.close()
            self._self_hosted_saver = None
