"""What the arithmetic of a training step requires, from shapes alone.

The yardstick's own count, so that no later PR can move a numerator:
``train_flops_per_token`` is the count of the configuration's family
(``families/<family>.py`` ``flops_per_token(shape)``: layers of
several kinds, a stack run several times, experts held) and, where the
family gives none, the count of a stack whose layers are all alike:
6 x (parameters a token is multiplied by: ``layers`` x
``layer_matmul_params``, which for OLMoE counts the experts a token is
sent to and not all of them, and the loss head's rows) + attention,
with the causal half and the sliding window taken off, nothing
recomputed. The program's own
``flops_per_token`` (models/gpt.py, models/llama.py) counts
``12*L*T*E`` with no causal discount and ``block_size`` where
Mistral's window is 4096; it overstates GPT-2 124M at T=1024 by 6.6%.

The sizes come from the configuration file through its family's
``shape`` (``families/<family>.py``), and one kernel call's operations
and bytes from ``kernel_work/<kernel>.py``: a new family or kernel is
a new file, and nothing here knows a name. No JAX.
"""

from __future__ import annotations

import importlib


def _family(config: dict):
    return importlib.import_module(f"benchmark.families.{config['family']}")


def shape_of(config: dict) -> dict:
    """The configuration's sizes under one set of names, from the
    module of its family."""
    return _family(config).shape(config)


def kernel_work(kernel: str, config: dict, batch_rows: int) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` on one device,
    from ``kernel_work/<kernel>.py``."""
    module = importlib.import_module(f"benchmark.kernel_work.{kernel}")
    return module.work(shape_of(config), batch_rows)


def mean_keys(seq_len: int, window=None) -> float:
    """Mean number of keys a query attends to under a causal mask:
    query i (0-based) sees min(i + 1, window) keys."""
    t = int(seq_len)
    if not window or window >= t:
        return (t + 1) / 2.0
    w = int(window)
    return (w * (w + 1) / 2.0 + (t - w) * w) / t


def matmul_params(config: dict) -> int:
    """Parameters that a token is multiplied by: every block's
    matrices and the loss head (tied or not, it is one V x E product;
    the embedding lookup multiplies nothing)."""
    s = shape_of(config)
    return s["layers"] * s["layer_matmul_params"] + s["vocab_rows"] * s["embd"]


def attention_flops_per_token(config: dict) -> float:
    """Forward QK^T and PV are 2 x 2 x (heads x head_dim) x keys each
    token; the backward costs twice the forward: 12 x E x keys."""
    s = shape_of(config)
    return (
        12.0 * s["layers"] * s["heads"] * s["head_dim"]
        * mean_keys(s["seq_len"], s["window"])
    )


def train_flops_per_token(config: dict) -> float:
    """What the passes of a whole step require for a token, nothing
    recomputed and no row of padding counted: the family's own count
    where it gives one, else the count of layers all alike."""
    own = getattr(_family(config), "flops_per_token", None)
    if own is not None:
        return own(shape_of(config))
    return 6.0 * matmul_params(config) + attention_flops_per_token(config)


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which limit sets it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "compute" if t_flops >= t_bytes else "memory",
    }
