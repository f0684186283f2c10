"""How sharp is (a) of ``correct``? Run once on the chip by the PR that
sets or changes the reference tolerance; the numbers go into PERF.md.

    python3 benchmark/calibrate_reference.py --workload <cell> \
        --seeds 11,12,13 --rows 4 --out chiprun_out/cal.json

For each seed it makes the cell's weights as the benchmark does, and
for each of ``rows`` seeded sequences reads the plain reference's loss
and the system's loss, as it is and deliberately broken:

* ``bf16``: the system as it is (bf16 with f32 accumulation);
* ``e4m3``, ``e5m2``: the system on weights rounded to an 8-bit float
  (3 and 2 bits of mantissa) while the reference keeps the bf16 ones:
  the least that computing in a lower precision would do;
* ``no_window``: a sliding-window configuration with the window off.

A sound tolerance passes every ``bf16`` reading with room and fails
the others. Rows are read one at a time so that the statistic the
check uses (``kinds/common.reference_error``) can be tried on 1..rows
of them.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="3000000019,11,12")
    p.add_argument("--rows", type=int, default=4)
    p.add_argument("--out", default="")
    p.add_argument("--cells-root", default=HERE)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import cell as cell_files
    from benchmark import traffic_gen
    from benchmark.kinds import common
    from dlrover_tpu.trainer import jax_env

    jax_env.enable_compile_cache()
    cell = cell_files.load_cell(args.workload, args.cells_root)
    config, traffic = cell["config"], cell["traffic"]
    families = importlib.import_module(f"benchmark.families.{config['family']}")
    family = families.build(config)
    variants = {"bf16": (family["loss"], None)}
    for name, dtype in (("e4m3", jnp.float8_e4m3fn), ("e5m2", jnp.float8_e5m2)):
        variants[name] = (family["loss"], dtype)
    if config.get("sliding_window"):
        variants["no_window"] = (
            families.build(dict(config, sliding_window=None))["loss"], None
        )
    losses = {k: jax.jit(fn) for k, (fn, _) in variants.items()}

    def rounded(params, dtype):
        return jax.tree.map(
            lambda x: x.astype(dtype).astype(x.dtype) if x.ndim >= 2 else x,
            params,
        )

    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "seeds": []}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        params = jax.jit(family["init"])(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        data = traffic_gen.token_stream(traffic["stream"], family["vocab"], seed)
        tok, tgt = traffic_gen.reference_batch(data, family["seq_len"], args.rows)
        rec = {"seed": seed, "reference": [], **{k: [] for k in variants}}
        for r in range(args.rows):
            t, g = jnp.asarray(tok[r: r + 1]), jnp.asarray(tgt[r: r + 1])
            rec["reference"].append(float(family["reference_loss"](params, t, g)))
            for k, (_, dtype) in variants.items():
                ps = params if dtype is None else rounded(params, dtype)
                rec[k].append(float(losses[k](ps, t, g)))
        rec["seconds"] = time.time() - t0
        for k in variants:
            rec[k + "_error"] = {
                n: common.reference_error(rec[k][:n], rec["reference"][:n])
                for n in range(1, args.rows + 1)
            }
        out["seeds"].append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
