"""Flash-checkpoint staging benchmark -> CKPT_r05.json (VERDICT r4
missing #5).

Measures, on a >= 1 GB state, what the reference publishes for its
async checkpoint design (/root/reference/docs/design/
async-checkpoint.md:31-40 — 2.3 s device->shm staging vs 6.5 s
blocking serialize+write for a 3 GB model):

* ``stage_s``       — save_to_memory: device->host copy + shm write,
                      the ONLY time the train loop is blocked;
* ``blocking_s``    — the alternative a trainer without the shm path
                      pays inline: device->host + pack_shard_file
                      serialize + storage write of the same state;
* ``persist_s``     — async latency from save_to_storage returning to
                      the agent's commit landing (trainer runs
                      meanwhile);
* ``restore_s``     — engine.load_flat of the committed checkpoint.

Runs on whatever backend jax has (the artifact records it): on the
TPU host the device->host copy is the real HBM transfer; on CPU it
degenerates to memcpy, which still measures the shm-vs-serialize
design point (serialization cost dominates the blocking path either
way).

Run:  python -u tools/ckpt_bench.py [--gb 1.0]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import uuid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gb", type=float, default=1.0,
                    help="state size in GiB (default 1.0)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(REPO, "CKPT_r05.json"))
    args = ap.parse_args()

    os.environ.setdefault("DLROVER_TPU_JOB_NAME",
                          f"ckptbench{uuid.uuid4().hex[:6]}")
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        CheckpointEngine,
        pack_shard_file,
    )

    ckpt_dir = "/tmp/ckpt_bench/store"
    shutil.rmtree("/tmp/ckpt_bench", ignore_errors=True)
    os.makedirs(ckpt_dir, exist_ok=True)

    # A transformer-shaped state: a handful of big matmul weights plus
    # small vector leaves (biases/norms) so the pytree walk and entry
    # planning see realistic leaf-count structure, not one blob.
    leaf_mb = 64
    n_big = max(1, int(args.gb * 1024) // leaf_mb)
    rows = leaf_mb * 1024 * 1024 // (4 * 4096)
    key = jax.random.PRNGKey(0)
    state = {
        f"layer{i}": {
            "w": jax.random.normal(
                jax.random.fold_in(key, i), (rows, 4096), jnp.float32
            ),
            "b": jnp.ones((4096,), jnp.float32),
            "scale": jnp.float32(1.0),
        }
        for i in range(n_big)
    }
    jax.block_until_ready(state)
    nbytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state)
    )
    print(f"[ckpt] state: {nbytes / 2**30:.2f} GiB, "
          f"{len(jax.tree_util.tree_leaves(state))} leaves, "
          f"backend={jax.default_backend()}", flush=True)

    saver = AsyncCheckpointSaver(
        checkpoint_dir=ckpt_dir, local_shard_num=1, global_shard_num=1,
        commit_timeout=300.0,
    )
    saver.start()
    engine = CheckpointEngine(ckpt_dir, use_agent=True)
    rec: dict = {
        "state_gib": round(nbytes / 2**30, 3),
        "leaves": len(jax.tree_util.tree_leaves(state)),
        "backend": jax.default_backend(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        # -- staging (save_to_memory): first call creates/maps the shm
        # segment; steady state is the repeat. Report both.
        t0 = time.perf_counter()
        assert engine.save_to_memory(0, state)
        first = time.perf_counter() - t0
        stages = []
        for i in range(args.repeats):
            t0 = time.perf_counter()
            assert engine.save_to_memory(i + 1, state)
            stages.append(time.perf_counter() - t0)
        rec["stage_first_s"] = round(first, 3)
        rec["stage_s"] = round(min(stages), 3)
        rec["stage_all_s"] = [round(s, 3) for s in stages]
        print(f"[ckpt] save_to_memory: first={first:.2f}s "
              f"steady={min(stages):.2f}s", flush=True)

        # -- blocking baseline: device->host + serialize + write, the
        # inline cost a trainer without shm staging pays every save.
        blocking = []
        for i in range(args.repeats):
            t0 = time.perf_counter()
            arrays, total = engine._stage(state)
            payload = bytearray(total)
            for e, host in arrays:
                payload[e.offset:e.offset + e.nbytes] = (
                    host.tobytes() if not host.flags["C_CONTIGUOUS"]
                    else memoryview(host).cast("B")
                )
            data = pack_shard_file(0, [e for e, _ in arrays], {},
                                   bytes(payload))
            with open(f"/tmp/ckpt_bench/blocking_{i}.ckpt", "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            blocking.append(time.perf_counter() - t0)
            os.unlink(f"/tmp/ckpt_bench/blocking_{i}.ckpt")
        rec["blocking_s"] = round(min(blocking), 3)
        rec["blocking_all_s"] = [round(s, 3) for s in blocking]
        rec["blocking_over_stage"] = round(
            min(blocking) / max(min(stages), 1e-9), 2
        )
        print(f"[ckpt] blocking serialize+write: {min(blocking):.2f}s "
              f"({rec['blocking_over_stage']}x staging)", flush=True)

        # -- async persist latency: trainer-side call returns after
        # staging; the agent writes + commits in the background.
        step = args.repeats + 1
        t0 = time.perf_counter()
        assert engine.save_to_storage(step, state)
        returned = time.perf_counter() - t0
        assert engine.wait_persisted(step, timeout=300.0)
        persisted = time.perf_counter() - t0
        rec["save_to_storage_returns_s"] = round(returned, 3)
        rec["persist_s"] = round(persisted, 3)
        print(f"[ckpt] save_to_storage returned in {returned:.2f}s, "
              f"committed at {persisted:.2f}s", flush=True)

        # -- restore
        t0 = time.perf_counter()
        step_got, flat, _extra = engine.load_flat(step)
        restore = time.perf_counter() - t0
        assert step_got == step
        got = sum(v.nbytes for v in flat.values())
        assert got == nbytes, (got, nbytes)
        rec["restore_s"] = round(restore, 3)
        print(f"[ckpt] restore (load_flat): {restore:.2f}s", flush=True)

        # Sanity: restored bytes match a source leaf.
        import numpy as np

        np.testing.assert_array_equal(
            flat["layer0/w"], np.asarray(state["layer0"]["w"])
        )
        rec["verified"] = True
    finally:
        engine.close()
        saver.close()
        for shm in saver._shms:
            try:
                shm.unlink()
            except Exception:  # noqa: BLE001
                pass
        shutil.rmtree("/tmp/ckpt_bench", ignore_errors=True)

    json.dump(rec, open(args.out, "w"), indent=1)
    print(f"[ckpt] wrote {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
