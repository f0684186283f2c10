"""LoRA fine-tuning a Llama model (BASELINE.md tracked config
"Llama-2-7B FSDP-equivalent via auto-accelerate", fine-tune flavor).

Reference counterpart: /root/reference/atorch/examples/llama2/
fsdp_llama2.py --peft_type lora (HF model + peft + atorch FSDP). Here
the whole recipe is native:

* model: models/llama.py (scan backbone, RoPE/GQA/SwiGLU), sized by
  --preset (tiny for CPU smoke runs, 7b for a real pod);
* weights: random init, or converted from an HF checkpoint via
  models/hf_convert.llama_params_from_hf;
* parallelism: the same (mesh, logical-axis rules) pair as
  pretraining — base params sharded over fsdp/tensor, frozen;
* LoRA: models/lora.py pytree transform; ONLY the LoRA tree carries
  optimizer state, so optimizer memory is ~1% of full fine-tuning.

Run:  python examples/llama_lora/train.py [--steps 20] [--rank 8]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

# Running as a script puts examples/llama_lora (not the repo root)
# first on sys.path; fix up here rather than ask for PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

# CPU-mesh by default (the env may preset a TPU platform; the tiny
# preset is a smoke run). Pass --tpu to use the ambient platform.
if "--tpu" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from dlrover_tpu.models import llama, lora  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402
from dlrover_tpu.parallel.sharding import tree_shardings  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "7b"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument(
        "--tpu", action="store_true",
        help="run on the ambient platform instead of forcing CPU",
    )
    args = ap.parse_args()

    cfg = (
        llama.LlamaConfig.tiny()
        if args.preset == "tiny"
        else llama.LlamaConfig.llama2_7b()
    )
    n_dev = len(jax.devices())
    mesh = build_mesh(
        MeshConfig(data=max(n_dev // 2, 1), fsdp=min(2, n_dev))
    )

    # Frozen base params, sharded by the standard rule table.
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    shardings = tree_shardings(mesh, llama.param_logical_axes(cfg))
    params = jax.tree.map(jax.device_put, params, shardings)

    lcfg = lora.LoraConfig(rank=args.rank)
    lp = lora.init_lora(params, lcfg, jax.random.PRNGKey(1))
    print(
        f"base params: {sum(x.size for x in jax.tree.leaves(params)):,}"
        f"  trainable (LoRA): {lora.num_trainable(lp):,}"
    )

    opt = optax.adamw(args.lr)
    opt_state = opt.init(lp)

    def loss_fn(lp_, tokens, targets):
        eff = lora.apply(params, lp_, lcfg)
        return llama.loss_fn_fused(
            eff, tokens, targets, cfg, num_chunks=4
        )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(lp_, opt_state, tokens, targets):
        loss, g = jax.value_and_grad(loss_fn)(lp_, tokens, targets)
        updates, opt_state = opt.update(g, opt_state, lp_)
        return optax.apply_updates(lp_, updates), opt_state, loss

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, cfg.block_size)),
        jnp.int32,
    )
    targets = jnp.roll(tokens, -1, axis=1)

    t0 = time.time()
    for i in range(args.steps):
        lp, opt_state, loss = step(lp, opt_state, tokens, targets)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f}")
    print(f"done in {time.time() - t0:.1f}s")

    merged = lora.merge(params, lp, lcfg)  # export-ready weights
    del merged
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
