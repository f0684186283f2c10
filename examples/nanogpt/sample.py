"""Sample from a nanoGPT checkpoint trained by examples/nanogpt/train.py.

Counterpart of the reference example's generate loop
(/root/reference/examples/pytorch/nanogpt/train.py wraps the same GPT;
nanoGPT upstream ships sample.py): restores the latest flash
checkpoint and decodes with the KV-cache sampler
(models/generate.py — one lax.scan, no per-token dispatch).

    python examples/nanogpt/sample.py --checkpoint-dir /tmp/... \
        [--tokens 64] [--temperature 0.8] [--top-k 40]
"""

from __future__ import annotations

import argparse
import os
import sys

# Running as a script puts examples/nanogpt (not the repo root) first
# on sys.path; fix up here rather than ask for PYTHONPATH.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

if "--tpu" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import generate, gpt  # noqa: E402
from dlrover_tpu.trainer.flash_checkpoint import Checkpointer  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--checkpoint-dir", required=True)
    p.add_argument("--tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=40)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="model config used by train.py --smoke")
    p.add_argument("--block-size", type=int, default=128)
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "agd", "adam8bit", "adam4bit"])
    p.add_argument("--tpu", action="store_true")
    args = p.parse_args()

    # Mirror train.py's model + optimizer construction exactly: the
    # checkpoint holds the (params, opt_state) tuple it saves.
    if args.smoke:
        cfg = gpt.GPTConfig(
            vocab_size=256, block_size=args.block_size, n_layer=2,
            n_head=2, n_embd=64, dtype=jnp.float32, remat=False,
        )
    else:
        cfg = gpt.GPTConfig.nano()

    from dlrover_tpu.accelerate import make_optimizer

    # train.py uses a flat lr (no schedule/clipping), so the bare
    # factory reconstructs its checkpoint layout; a schedule would
    # add opt-state leaves and need the same kwargs here.
    opt = make_optimizer(args.optimizer, 3e-4)
    like = jax.eval_shape(
        lambda k: (
            gpt.init_params(k, cfg),
            opt.init(gpt.init_params(k, cfg)),
        ),
        jax.random.PRNGKey(0),
    )
    ckpt = Checkpointer(args.checkpoint_dir)
    try:
        state = ckpt.load_checkpoint(like)
        if state is None:
            print(
                f"no committed checkpoint in {args.checkpoint_dir}",
                file=sys.stderr,
            )
            return 1
        params = state[0]
        step = ckpt.last_restored_step
    finally:
        ckpt.close()

    prompt = jnp.zeros((1, 1), jnp.int32)  # char 0 = start
    out = generate.generate(
        params, cfg, prompt, max_new_tokens=args.tokens,
        temperature=args.temperature, top_k=args.top_k,
        key=jax.random.PRNGKey(args.seed),
    )
    ids = [int(t) for t in out[0]]
    text = "".join(chr(max(32, min(126, i))) for i in ids)
    print(f"# step {step}, {args.tokens} tokens")
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
