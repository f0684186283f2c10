"""Family ``phi4_flash`` broken on purpose, one path a control (cells
under ``benchmark/controls/phi4_flash_cells``):

* ``no_carry``: the selective scan starts every chunk from a zero
  state (each chunk is scanned as a sequence of its own), so nothing
  crosses a chunk boundary;
* ``no_diff``: the second softmax map of every pair is left out,
  ``a = P1 V``: plain attention on the pair's first heads;
* ``window_off``: the window layer sees the whole prefix;
* ``gmu_no_memory``: the gated memory unit's memory is all ones, a
  plain gated projection of the layer's own input.

No control puts the cross-attention layer under the window of 512,
which the published layer does not have (``cross_own_window``): on the
chip it read under the tolerance on three seeds of eight at the
initial values the cell ships with (1.8e-4 to 9.5e-4 against 3e-4) and
on more at every other ``initializer_range`` tried (PERF.md section 6,
PR 64), and a control that passes is no control. It is the stack's
last layer and only its own MLP stands between it and the head;
``tests/test_phi4_flash.py`` holds the cross layers' mask, full and
causal on another layer's keys, to the reference on the CPU.

The program has no switch for any of these: the first puts a broken
scan in ``dlrover_tpu.ops.selective_scan``'s place and the last a
wrapped ``gmu_mixer`` in ``dlrover_tpu.models.phi4_flash``'s while the
loss is traced; ``no_diff`` and ``window_off`` hand the loss an
attention callable. ``no_diff``'s returns zeros for every second call
of a trace: differential attention calls it twice a layer, ``(q1, k1,
V)`` then ``(q2, k2, V)``, and ``a1 - lam x 0`` is the first map
alone.
"""

from __future__ import annotations

import functools

from benchmark.controls.granite_hybrid import _in_place_of

NAMES = ("no_carry", "no_diff", "window_off", "gmu_no_memory")


def _scan_without_carry(scan):
    def broken(xs, dt, A, B, C, D, chunk=64):
        bsz, t = xs.shape[:2]
        cut = lambda v: v.reshape((bsz * (t // chunk), chunk) + v.shape[2:])
        y = scan(cut(xs), cut(dt), A, cut(B), cut(C), D, chunk=chunk)
        return y.reshape(xs.shape)

    return broken


def _first_map_alone(attn_fn):
    calls = [0]

    def attend(q, k, v, **kw):
        import jax.numpy as jnp

        calls[0] += 1
        out = attn_fn(q, k, v, **kw)
        return out if calls[0] % 2 else jnp.zeros_like(out)

    return attend


def broken(name: str, loss):
    """``loss`` (params, tokens, targets), a partial of the program's
    loss on its configuration, with the path ``name`` says broken."""
    from dlrover_tpu.models import phi4_flash as model
    from dlrover_tpu.ops import selective_scan as scan_module

    if name not in NAMES:
        raise ValueError(f"no control {name!r}: one of {NAMES}")
    attn_fn = model.default_attention_for(loss.keywords["cfg"])
    if name == "no_diff":
        return functools.partial(loss, attn_fn=_first_map_alone(attn_fn))
    if name == "window_off":

        def every_key(q, k, v, window=None, **kw):
            return attn_fn(q, k, v, **kw)

        return functools.partial(loss, attn_fn=every_key)
    if name == "no_carry":
        swap = (scan_module, "selective_scan",
                _scan_without_carry(scan_module.selective_scan))
    else:
        import jax.numpy as jnp

        honest_gmu = model.gmu_mixer
        swap = (model, "gmu_mixer", lambda u, lp, memory: honest_gmu(
            u, lp, jnp.ones_like(memory)
        ))

    def traced_broken(*args):
        with _in_place_of(*swap):
            return loss(*args)

    return traced_broken
