"""Strategy: the unit of acceleration search.

The whole optimization space of the reference's opt_lib (13 methods,
atorch/auto/opt_lib/optimization_library.py:38-56) maps to this one
record: zero1/2/3+fsdp -> the ``fsdp`` mesh axis; tensor_parallel ->
``tensor``; pipeline_parallel -> ``pipe``; sequence parallel ->
``seq``; amp_native/half -> dtype policy; checkpoint -> remat policy;
module_replace (flash-attn swap) -> the model's attention config.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Strategy:
    mesh_shape: Tuple[Tuple[str, int], ...]  # (("data",4),("fsdp",2),...)
    # bool or a named policy from accelerate/remat.py
    # ("none"|"full"|"attention"|"dots"|"offload")
    remat: object = True
    dtype: str = "bfloat16"  # compute/weights dtype policy
    optimizer: str = "adamw"  # adamw | agd | adam8bit | adam4bit | sgd
    micro_batch_size: int = 8
    # Sequence-parallel family when the mesh has a seq axis:
    # "auto" (a2a when heads-per-tensor-shard divides by seq shards,
    # ring otherwise — parallel/seq_attention.py), or forced
    # "ring"/"a2a".
    seq_impl: str = "auto"

    @property
    def mesh_dict(self) -> Dict[str, int]:
        return dict(self.mesh_shape)

    def _remat_name(self) -> str:
        from dlrover_tpu.accelerate.remat import canonical

        return canonical(self.remat)  # validates; fails fast on typos

    def name(self) -> str:
        mesh = "x".join(f"{a}{s}" for a, s in self.mesh_shape if s > 1)
        sp = "" if self.seq_impl == "auto" else f"-sp:{self.seq_impl}"
        return (
            f"{mesh or 'single'}-{self.dtype}"
            f"-remat:{self._remat_name()}-{self.optimizer}"
            f"-mb{self.micro_batch_size}{sp}"
        )

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(s: str) -> "Strategy":
        d = json.loads(s)
        d["mesh_shape"] = tuple(
            (a, int(n)) for a, n in d["mesh_shape"]
        )
        return Strategy(**d)


def _factorizations(n: int, n_axes: int) -> List[Tuple[int, ...]]:
    """All ways to write n as an ordered product of n_axes factors."""
    if n_axes == 1:
        return [(n,)]
    out = []
    for f in range(1, n + 1):
        if n % f == 0:
            for rest in _factorizations(n // f, n_axes - 1):
                out.append((f,) + rest)
    return out


def candidate_strategies(
    n_devices: int,
    axes: Tuple[str, ...] = ("data", "fsdp", "seq", "tensor", "pipe"),
    micro_batch_sizes: Tuple[int, ...] = (4, 8, 16),
    dtypes: Tuple[str, ...] = ("bfloat16",),
    optimizers: Tuple[str, ...] = ("adamw",),
    remats: Tuple[object, ...] = (False, "attention", True),
    max_tensor: int = 8,
    max_pipe: int = 8,
    seq_impls: Tuple[str, ...] = ("auto",),
) -> List[Strategy]:
    """Enumerate the raw candidate grid (the reference's
    CombinationAlgorithm, auto/engine/sg_algo/combination_sg.py:16).

    The default grid spans every mesh factorization over
    data/fsdp/seq/tensor/pipe x remat policy x micro-batch — hundreds
    of candidates at 8 devices. That breadth is affordable because
    nothing here compiles: the memory model prunes, the module
    profiler's roofline prior ranks, and only the top handful are
    dry-run (auto_accelerate max_dry_runs). A seq axis without ring
    attention stays CORRECT under GSPMD (sharding annotations never
    change semantics, XLA inserts the collectives); the dry-run
    decides whether it is fast."""
    out = []
    for factors in _factorizations(n_devices, len(axes)):
        shape = tuple(zip(axes, factors))
        d = dict(shape)
        if d.get("tensor", 1) > max_tensor:
            continue
        if d.get("pipe", 1) > max_pipe:
            continue
        # The seq_impl knob only distinguishes candidates when a seq
        # axis exists (otherwise every family degenerates identically).
        sps = seq_impls if d.get("seq", 1) > 1 else ("auto",)
        for mb, dt, opt, rm, sp in itertools.product(
            micro_batch_sizes, dtypes, optimizers, remats, sps
        ):
            out.append(
                Strategy(
                    mesh_shape=shape,
                    remat=rm,
                    dtype=dt,
                    optimizer=opt,
                    micro_batch_size=mb,
                    seq_impl=sp,
                )
            )
    return out
