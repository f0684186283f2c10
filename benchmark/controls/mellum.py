"""Family ``mellum`` broken on purpose, one path a control (cells under
``benchmark/controls/mellum_cells``):

* ``window_off``: the sliding layers see every key up to the query;
* ``yarn_off``: the full layer rotates by the sliding layers' plain
  table (no blend of frequencies, no ``attention_factor``);
* ``pattern_shifted``: the full layer stands first in the period and
  the three sliding layers behind it, each layer on the weights of its
  own place in the stack.

No control breaks the router's weights: the chosen experts' softmax
values used as they are, not divided by their sum (``no_renorm``), read
under the tolerance on one seed of three or more at every
``initializer_range`` tried on the chip (7.1e-5 at 0.02; PERF.md
section 6, PR 57), and a control that passes is no control.
``tests/test_mellum.py`` holds the renormalised weights and the four
shares to the reference on the CPU. None rounds the weights to 8 bits
either: as a cell of its own that read under the tolerance on one seed
of three (2.1e-4), so the lower precisions are read by
``benchmark/calibrate_reference.py --workload mellum2-12b-a2.5b.steady``
(``e4m3``, ``e5m2``, and the window off, in one process) and not
shipped as a cell.

The check reads two sequences, and a broken path's error on a sequence
is a draw around zero, so a control now and then reads far under its
usual size: over every seed tried on the chip ``pattern_shifted`` was
refused on 22 of 22, ``window_off`` on 25 of 26 and ``yarn_off`` on 19
of 22 (PERF.md section 6, PR 57). One ``"correct": true`` from one of
the last two is no alarm; several are. ``tests/test_mellum.py`` holds
the program's YaRN table to the reference's and to the formula by hand,
and the band to the reference's mask: those paths' guards.

The program has no switch for any of these: each traces the program's
loss on a configuration that states another model, or hands it an
attention callable that drops the window.
"""

from __future__ import annotations

import dataclasses
import functools

NAMES = ("window_off", "yarn_off", "pattern_shifted")


def broken(name: str, loss):
    """``loss`` (params, tokens, targets), a partial of the program's
    loss on its configuration, with the path ``name`` says broken."""
    from dlrover_tpu.models import mellum as model

    if name not in NAMES:
        raise ValueError(f"no control {name!r}: one of {NAMES}")
    cfg = loss.keywords["cfg"]

    def on(other):
        return functools.partial(loss.func, **{**loss.keywords, "cfg": other})

    if name == "window_off":
        attn_fn = model.default_attention_for(cfg)

        def every_key(q, k, v, window=None, **kw):
            return attn_fn(q, k, v, **kw)

        return functools.partial(loss, attn_fn=every_key)
    if name == "yarn_off":
        return on(dataclasses.replace(cfg, rope_full=cfg.rope_sliding))
    period = len(cfg.period)
    moved = dataclasses.replace(
        cfg, layer_types=tuple(
            kind
            for start in range(0, cfg.n_layer, period)
            for kind in (
                cfg.layer_types[start + period - 1],
                *cfg.layer_types[start: start + period - 1],
            )
        ),
    )
    shifted = on(moved)

    def traced_shifted(params, *batch):
        # The same weights a place in the stack, under the names the
        # moved pattern gives the places.
        periods = dict(zip(
            moved.layer_names,
            (params["periods"][name] for name in cfg.layer_names),
        ))
        return shifted(dict(params, periods=periods), *batch)

    return traced_shifted
