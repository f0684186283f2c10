"""Each cell's step, lowered for a described ``v5e:2x2``, as one hash.

A refactor that changes no computation changes no lowered step: that
is the proof a PR offers for it (take the hashes on the parent and on
the change), and what the compile cache keys a step by. Nothing is
compiled and nothing runs, so it needs no chip and a few seconds a
step. For each step the compile tests build (tests/tpu_steps.py
``STEPS``: the ten cells' families at the cells' shapes) it prints
one JSON line:

    {"step": "kimi", "bytes": ..., "custom_calls": 68,
     "bodies_naming_a_file": 0, "sha256": "..."}

``bodies_naming_a_file`` counts the Pallas kernels whose serialized
body (``custom_call_config.body``, which is no metadata: JAX keeps it
in the cache's key) holds the name of a ``.py`` file. It reads 0
since ``trainer/jax_env.enable_compile_cache`` leaves no Python frame
in a location; were it not 0, an added comment line would re-key the
step. ``ONLY=<name>[,<name>]`` lowers those steps alone, in the order
given.

    JAX_PLATFORMS=cpu python tools/step_hash.py
    ONLY=kimi,olmoe JAX_PLATFORMS=cpu python tools/step_hash.py
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re

import _repo_path  # noqa: F401

# A described chip is compiled for, not attached: stay off any real one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# MLIR prints a quote inside a string attribute as \22.
_BODY = re.compile(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22")


def describe(text: str) -> dict:
    """What a lowered step's text says of its identity."""
    bodies = [base64.b64decode(b) for b in _BODY.findall(text)]
    calls = text.count("@tpu_custom_call")
    if len(bodies) != calls:
        raise ValueError(
            f"{calls} Mosaic calls and {len(bodies)} bodies found: the "
            "text's form has changed and the count below would say nothing"
        )
    return {
        "bytes": len(text.encode()),
        "custom_calls": calls,
        "bodies_naming_a_file": sum(b".py" in body for body in bodies),
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
    }


def main() -> int:
    from dlrover_tpu.trainer.jax_env import enable_compile_cache
    from tests import tpu_steps

    # The program's own JAX options, as every process that lowers a
    # step for the chip sets them.
    enable_compile_cache()
    names = [n for n in os.environ.get("ONLY", "").split(",") if n]
    unknown = set(names) - set(tpu_steps.STEPS)
    if unknown:
        raise SystemExit(
            f"ONLY names {sorted(unknown)}; the steps are "
            f"{list(tpu_steps.STEPS)}"
        )
    with tpu_steps.described_v5e() as topo, tpu_steps.kernels_for_the_chip():
        for name in names or tpu_steps.STEPS:
            text = tpu_steps.lower_step(name, topo).as_text()
            print(json.dumps({"step": name, **describe(text)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
