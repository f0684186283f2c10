"""Family ``ouro`` broken on purpose, one path a control
(cells under ``benchmark/controls/ouro_cells``: ``run.py --cells-root
benchmark/controls/ouro_cells --workload ouro-2.6b.<control>``):

* ``three_passes``: the program runs the stack one time fewer than the
  configuration (and so the reference) says;
* ``no_gate``: the gate is ignored: every pass gets the same share of
  every position (a uniform exit distribution, and its entropy);
* ``no_pass_norm``: the norm that closes a pass is left out of the
  loop: the head and the gate still read the normed state, the next
  pass starts from the stack's raw output.

The program has no switch for any of these: the first builds the loss
for another ``ut_steps``, the others put a broken function in
``dlrover_tpu.models.ouro``'s place while the loss is traced.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmark.controls.granite_hybrid import _in_place_of

NAMES = ("three_passes", "no_gate", "no_pass_norm")


def _uniform_exit(params, hs):
    import jax.numpy as jnp

    b, s, t, _ = hs.shape
    return (
        jnp.full((b, s, t), 1.0 / s, jnp.float32),
        jnp.full((b, t), jnp.log(jnp.float32(s))),
    )


def _norm_not_fed_back(close_pass):
    def broken(x, params, cfg):
        _, out = close_pass(x, params, cfg)
        return x, out

    return broken


def broken(name: str, cfg):
    """models/ouro.py's fused loss (params, tokens, targets) for
    ``cfg`` with the path ``name`` says broken."""
    from dlrover_tpu.models import ouro as model

    if name == "three_passes":
        return functools.partial(
            model.loss_fn_fused,
            cfg=dataclasses.replace(cfg, ut_steps=cfg.ut_steps - 1),
        )
    if name == "no_gate":
        swap = ("exit_distribution", _uniform_exit)
    elif name == "no_pass_norm":
        swap = ("_close_pass", _norm_not_fed_back(model._close_pass))
    else:
        raise ValueError(f"no control {name!r}: one of {NAMES}")

    def traced_broken(*args):
        with _in_place_of(model, *swap):
            return model.loss_fn_fused(*args, cfg=cfg)

    return traced_broken
