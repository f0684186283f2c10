"""CTR training on the PS-elastic sparse path.

The BASELINE.md tracked config "KV-embedding CTR sparse model (PS
elastic path)" end-to-end: hashed categorical features are looked up
from KvVariable tables sharded across PS nodes
(sparse/ps_server.py), the dense tower runs in JAX, sparse rows train
with a fused C++ group-lasso optimizer (native/kv_store.cc
kv_sparse_apply_group_adam — ref tfplus group_adam.py), and the dense
tower with optax. Reference counterpart: tfplus example/dcn/train.py
on TF parameter servers.

Run:  python examples/ctr/train.py [--steps 200] [--drill MODE]

--drill graceful kills one PS mid-training after a delta flush; the
survivor restores its partitions from the per-partition checkpoint
files and training continues with no lost embeddings (the sparse
analogue of the flash-checkpoint recovery drill).

--drill abrupt is the real PS-failover drill (ref: the estimator
executor's version-checked PS failover,
trainer/tensorflow/failover/tensorflow_failover.py:33): one PS dies
with NO flush and NO master notification. The training loop's next
sparse op blocks in the client's stale-map retry; the PsManager
liveness monitor detects the dead PS, rebalances its partitions onto
the survivors (restored from the last periodic delta flush), bumps the
map version, and the blocked client resumes. This example runs
UNFENCED (no stream barriers): an abrupt death loses the updates since
the last flush, so --flush-every bounds the loss window. With the
stream-barrier path (SparseTrainer barrier_every + a fenced client,
drilled by tools/stream_soak.py) the same kill loses ZERO updates —
the trainer replays its post-barrier window through the PS replay
fence, so flush cadence only bounds replay length, not loss.
--drill-json writes the recovery stats artifact.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

# Running as a script puts examples/ctr (not the repo root) first on
# sys.path; fix up here rather than ask for PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from dlrover_tpu import obs  # noqa: E402
from dlrover_tpu.data.prefetch import (  # noqa: E402
    make_input_pipeline,
)
from dlrover_tpu.master.ps_manager import PsManager  # noqa: E402
from dlrover_tpu.sparse.ps_client import DistributedKvClient  # noqa: E402
from dlrover_tpu.sparse.ps_server import PsServer  # noqa: E402

N_FIELDS = 8
EMB_DIM = 8
VOCAB_PER_FIELD = 1000


def synthetic_batch(rng, batch):
    """Hashed categorical ids [B, F] + labels from a hidden linear
    model over the id hashes (learnable -> loss must fall)."""
    ids = rng.integers(0, VOCAB_PER_FIELD, size=(batch, N_FIELDS))
    keys = ids + np.arange(N_FIELDS) * VOCAB_PER_FIELD  # field offset
    w = np.sin(np.arange(N_FIELDS) + 1.0)
    logit = (np.sin(ids * 0.01) * w).sum(axis=1)
    labels = (logit + 0.1 * rng.standard_normal(batch) > 0).astype(
        np.float32
    )
    return keys.astype(np.int64), labels


def dense_init(key):
    k1, k2 = jax.random.split(key)
    h = 32
    return {
        "w1": jax.random.normal(k1, (N_FIELDS * EMB_DIM, h)) * 0.1,
        "b1": jnp.zeros((h,)),
        "w2": jax.random.normal(k2, (h, 1)) * 0.1,
        "b2": jnp.zeros((1,)),
    }


def forward(dense, emb):  # emb: [B, F*D]
    x = jax.nn.relu(emb @ dense["w1"] + dense["b1"])
    return (x @ dense["w2"] + dense["b2"]).squeeze(-1)


def loss_fn(dense, emb, labels):
    logits = forward(dense, emb)
    return jnp.mean(
        optax.sigmoid_binary_cross_entropy(logits, labels)
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--n-ps", type=int, default=2)
    p.add_argument("--optimizer", default="group_adam")
    p.add_argument("--l21", type=float, default=1e-4)
    p.add_argument("--drill", nargs="?", const="graceful", default="",
                   choices=["graceful", "abrupt"],
                   help="kill one PS mid-run; training must survive. "
                   "graceful: flush + orderly removal. abrupt: no "
                   "flush, no notification -- the liveness monitor "
                   "must detect it and fail over")
    p.add_argument("--flush-every", type=int, default=25,
                   help="periodic delta-flush cadence (steps); bounds "
                   "the updates an abrupt PS death can lose")
    p.add_argument("--drill-json", default="",
                   help="write the drill recovery stats JSON here")
    p.add_argument("--kills", type=int, default=1,
                   help="soak mode: kill this many PS servers one "
                   "after another (recovery measured per kill; needs "
                   "n-ps > kills so a survivor remains)")
    p.add_argument("--max-ram-rows", type=int, default=0,
                   help=">0 enables the hybrid RAM/disk tier: at most "
                   "this many embedding rows stay resident per PS")
    args = p.parse_args(argv)
    if args.drill and not 1 <= args.kills < args.n_ps:
        p.error(
            f"--kills must be in [1, n_ps) = [1, {args.n_ps}), got "
            f"{args.kills}"
        )

    tmp = tempfile.mkdtemp(prefix="ctr_")
    mgr = PsManager(num_partitions=32)
    servers = {}
    for i in range(args.n_ps):
        ps = PsServer(
            node_id=i,
            checkpoint_dir=os.path.join(tmp, "sparse_ckpt"),
            embedding_dims={"emb": EMB_DIM},
            num_partitions=32,
            seed=100 + i,
            kv_options=(
                {
                    "disk_tier_path": tmp,
                    "max_ram_rows": args.max_ram_rows,
                }
                if args.max_ram_rows > 0
                else None
            ),
        )
        ps.start()
        servers[i] = ps
        mgr.register_ps(i, ps.addr)
    client = DistributedKvClient(
        lambda: mgr.partition_map, {"emb": EMB_DIM},
    )

    from dlrover_tpu.trainer.sparse_trainer import (
        SparseTrainer,
        make_ctr_loss_and_grads,
    )

    def ctr_loss(dense, emb, labels):
        emb = emb.reshape(-1, N_FIELDS * EMB_DIM)
        return loss_fn(dense, emb, labels)

    trainer = SparseTrainer(
        client,
        make_ctr_loss_and_grads(ctr_loss),
        optax.adamw(1e-2),
        dense_init(jax.random.PRNGKey(0)),
        table="emb",
        embedding_dim=EMB_DIM,
        sparse_optimizer=args.optimizer,
        sparse_lr=0.05,
        sparse_hparams={"l21": args.l21},
        flush_manager=mgr,
        flush_every=args.flush_every,
    )

    if args.drill == "abrupt":
        # Fast cadence so the in-process drill resolves in seconds;
        # production uses PsManager.start_liveness_monitor's defaults
        # (2 s ticks, 2 strikes, 3 s ping timeout -> ~10 s worst-case
        # detection, which the sparse client's ~39 s retry budget is
        # sized against — see ps_client.py).
        mgr.start_liveness_monitor(
            interval=0.5, failure_threshold=2, ping_timeout=2.0
        )

    rng = np.random.default_rng(0)
    # Kill points spread over the run (one at the midpoint for the
    # classic single-kill drill; evenly spaced for a soak) — each OFF
    # a flush boundary: an abrupt death right after a periodic flush
    # would lose zero updates and the drill would not exercise the
    # bounded-loss contract it documents.
    kill_steps = []
    if args.drill:
        for j in range(args.kills):
            ks = args.steps * (j + 1) // (args.kills + 1)
            # Walk forward past flush boundaries, collisions with an
            # earlier kill, and step 0 — never silently drop a kill.
            while ks < 1 or ks in kill_steps or (
                args.drill == "abrupt"
                and args.flush_every
                and ks % args.flush_every == 0
            ):
                ks += 1
            if ks > args.steps - 1:
                raise SystemExit(
                    f"--steps {args.steps} too small for --kills "
                    f"{args.kills} with --flush-every "
                    f"{args.flush_every}: kill {j} would land at "
                    f"step {ks} with no step left to measure its "
                    "recovery"
                )
            kill_steps.append(ks)
    # Batch synthesis (the host-side "collate" of this example) runs
    # in a prefetch worker, double-buffered ahead of the train loop —
    # the PS lookup/apply path never waits on input assembly.
    def batch_stream():
        while True:
            yield synthetic_batch(rng, args.batch)

    def stage(batch):
        keys, labels = batch
        return keys.ravel(), labels

    def h2d(batch):
        # Device placement split from the host collate so the staging
        # metrics attribute host vs H2D cost separately (see
        # docs/PERFORMANCE.md "Input pipeline and accumulation").
        keys_flat, labels = batch
        return keys_flat, jnp.asarray(labels)

    batches = make_input_pipeline(
        batch_stream(), stage_fn=stage, h2d_fn=h2d, name="ctr"
    )

    losses = []
    drill_stats = {}
    kills_done = []
    t0 = time.time()
    try:
        for step in range(1, args.steps + 1):
            step_start = time.time()
            keys_flat, labels = next(batches)
            # One high-level step: lookup -> grads -> dense update +
            # fused sparse apply + periodic flush, surviving PS failover
            # inside (trainer/sparse_trainer.py).
            loss = trainer.train_step(keys_flat, labels)
            losses.append(loss)

            if drill_stats.get("kill_step") == step - 1:
                # First full step after the kill: everything blocked in it
                # (stale-map retries + rebalance) is the recovery cost.
                t_unblocked = time.time()
                t_kill = drill_stats.pop("_kill_time")
                drill_stats["recovery_s"] = round(t_unblocked - t_kill, 3)
                drill_stats["map_version_after"] = (
                    mgr.partition_map.version
                )
                drill_stats["rows_after_recovery"] = client.table_size(
                    "emb"
                )
                fo = mgr.last_failover
                if args.drill == "abrupt" and fo is not None:
                    # Phase breakdown: liveness detection latency, the
                    # rebalance+restore inside remove_ps, and the blocked
                    # client's unblock-to-step-complete time.
                    drill_stats["phases"] = {
                        "detect_s": round(fo["t_detected"] - t_kill, 3),
                        "rebalance_restore_s": round(
                            fo["t_map_published"] - fo["t_detected"], 3
                        ),
                        "client_resume_s": round(
                            t_unblocked - fo["t_map_published"], 3
                        ),
                    }
                # PS failover into the obs event stream too (no-op unless
                # DLROVER_TPU_TRACE_FILE/DLROVER_TPU_TRACE is set): the
                # same trace file then explains worker AND PS recoveries.
                obs.event(
                    "ps.failover_recovered",
                    recovery_s=drill_stats["recovery_s"],
                    **(drill_stats.get("phases") or {}),
                )
                print(
                    f"DRILL: recovered in {drill_stats['recovery_s']}s "
                    f"(map v{drill_stats['map_version_before']} -> "
                    f"v{drill_stats['map_version_after']}, rows "
                    f"{drill_stats['rows_after_recovery']}, phases "
                    f"{drill_stats.get('phases')})"
                )
                kills_done.append(dict(drill_stats))

            if args.drill and step in kill_steps:
                vid = max(servers)
                victim = servers.pop(vid)
                rows = len(victim.table("emb"))
                drill_stats = {
                    "drill": f"ps_{args.drill}_kill",
                    "killed_ps": vid,
                    "kill_step": step,
                    "victim_rows": rows,
                    "rows_at_last_flush": trainer.last_flush_rows,
                    "map_version_before": mgr.partition_map.version,
                    "_kill_time": time.time(),
                }
                obs.event(
                    "ps.kill", ps=vid, step=step, mode=args.drill,
                    victim_rows=rows,
                )
                if args.drill == "graceful":
                    flushed = mgr.flush_all(step)
                    drill_stats["rows_at_last_flush"] = flushed
                    victim.stop()
                    mgr.remove_ps(vid)
                    print(
                        f"DRILL: flushed {flushed} rows, killed PS with "
                        f"{rows} rows at step {step}; survivors restore "
                        "from delta files"
                    )
                else:
                    # Abrupt: no flush, no notification. The next sparse
                    # op blocks until the liveness monitor fails it over.
                    victim.stop()
                    print(
                        f"DRILL: PS {vid} died abruptly at step {step} "
                        f"({rows} rows in memory, last flush "
                        f"{trainer.last_flush_rows}); waiting for liveness "
                        "failover"
                    )

            if step % 20 == 0 or step == 1:
                print(
                    f"step {step}: loss {loss:.4f} "
                    f"rows={client.table_size('emb')} "
                    f"({time.time() - step_start:.2f}s)",
                    flush=True,
                )
    finally:
        batches.close()

    head = float(np.mean(losses[:10]))
    tail = float(np.mean(losses[-10:]))
    dt = time.time() - t0
    print(
        f"done: {args.steps} steps in {dt:.1f}s, loss "
        f"{head:.4f} -> {tail:.4f}"
    )
    mgr.stop_liveness_monitor()
    client.close()
    for ps in servers.values():
        ps.stop()
    if args.drill_json and kills_done:
        import json

        # First kill's fields at top level (the one-shot drill
        # contract, tests/test_ps_drill_phases.py); a soak appends
        # the per-kill records and aggregates.
        out = dict(kills_done[0])
        out.pop("_kill_time", None)
        out.update(
            loss_head=round(head, 4),
            loss_tail=round(tail, 4),
            steps=args.steps,
            flush_every=args.flush_every,
            n_ps_before=args.n_ps,
        )
        if len(kills_done) > 1:
            for k in kills_done:
                k.pop("_kill_time", None)
            recs = [k["recovery_s"] for k in kills_done]
            out["kills"] = kills_done
            out["n_kills"] = len(kills_done)
            out["max_recovery_s"] = max(recs)
            out["mean_recovery_s"] = round(sum(recs) / len(recs), 3)
        with open(args.drill_json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"drill stats -> {args.drill_json}")
    if not tail < head:
        print("FAIL: loss did not decrease", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
