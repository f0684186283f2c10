"""Family ``granite_hybrid`` broken on purpose, one path a control:

* ``no_carry``: the scan starts every chunk from a zero state (each
  chunk is scanned as a sequence of its own), so nothing crosses a
  chunk boundary;
* ``state_bf16``: ``ssd_fwd``'s running state is rounded to bf16
  every time a chunk leaves it (the configuration states float32);
* ``no_D``: the skip ``D x`` is left out of every Mamba-2 layer;
* ``no_conv_bias``: the convolution's bias is left out.

The program has no switch for any of these: the first two put a
broken scan in ``dlrover_tpu.ops.ssd``'s place while the loss is
traced, the last two zero the leaf before the loss reads it.
"""

from __future__ import annotations

import contextlib
import inspect

NAMES = ("no_carry", "state_bf16", "no_D", "no_conv_bias")


@contextlib.contextmanager
def _in_place_of(module, name, value):
    honest = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, honest)


def _without(params, leaf):
    import jax.numpy as jnp

    return dict(params, runs={
        run: {k: jnp.zeros_like(v) if k == leaf else v for k, v in tree.items()}
        for run, tree in params["runs"].items()
    })


def _scan_without_carry(ssd):
    def scan(x, dt, A, B, C, D, chunk=256, interpret=None):
        bsz, t = x.shape[:2]
        cut = lambda v: v.reshape((bsz * (t // chunk), chunk) + v.shape[2:])
        y = ssd(cut(x), cut(dt), A, cut(B), cut(C), D, chunk, interpret)
        return y.reshape(x.shape)

    return scan


class _RoundedOnWrite:
    """A kernel's scratch reference whose writes are rounded to bf16."""

    def __init__(self, ref):
        self.ref, self.shape, self.dtype = ref, ref.shape, ref.dtype

    def __getitem__(self, idx):
        return self.ref[idx]

    def __setitem__(self, idx, value):
        import jax.numpy as jnp

        self.ref[idx] = value.astype(jnp.bfloat16).astype(value.dtype)


def _kernel_with_bf16_state(kernel):
    at = list(inspect.signature(kernel).parameters).index("state_scr")

    def broken(*refs, **static):
        refs = list(refs)
        refs[at] = _RoundedOnWrite(refs[at])
        return kernel(*refs, **static)

    return broken


def broken(name: str, loss):
    """``loss`` (params, tokens, targets) with the path ``name`` says
    broken; the other arguments pass through."""
    from dlrover_tpu.ops import ssd as ssd_module

    if name in ("no_D", "no_conv_bias"):
        leaf = {"no_D": "D", "no_conv_bias": "conv_b"}[name]
        return lambda params, *batch: loss(_without(params, leaf), *batch)
    if name == "no_carry":
        swap = ("ssd", _scan_without_carry(ssd_module.ssd))
    elif name == "state_bf16":
        swap = ("_fwd_kernel", _kernel_with_bf16_state(ssd_module._fwd_kernel))
    else:
        raise ValueError(f"no control {name!r}: one of {NAMES}")

    def traced_broken(*args):
        with _in_place_of(ssd_module, *swap):
            return loss(*args)

    return traced_broken
