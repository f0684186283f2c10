"""Family ``deepseek_v2`` broken on purpose, one path a control (cells
under ``benchmark/controls/deepseek_v2_cells``):

* ``rope_off``: nothing is rotated: the shared key part and the
  queries' last 64 columns go into attention as they come
  (models/kimi_linear.py's mixer);
* ``scale_plain``: the softmax scale is ``(d_n + d_r)^-0.5`` alone,
  YaRN's ``m^2`` left out (cos and sin keep their multiplier of 1);
* ``no_shared``: the shared experts are left out of every expert layer.

Each ships because ``correct`` refused it on every one of twelve seeds
on the chip at the configuration's ``initializer_range``, 0.06, twice
(my chip runs, PR 59; ``rms_rel`` of two sequences against the 3e-4
tolerance; first a scratch sweep that put every variant on one set of
weights, then ``benchmark/calibrate_reference.py`` on twelve more seeds
from the committed files, the cell and each control cell):
``rope_off`` 12 of twelve and 12 of twelve (5.3e-3 to 1.7e-2, then
3.7e-3 to 2.7e-2), ``scale_plain`` 12 of twelve and 12 of twelve
(5.7e-4 to 4.0e-3, then 3.3e-4 to 3.3e-3: the thinnest, 1.09 times the
tolerance on one seed and 1.2 on another), ``no_shared`` 12 of twelve
and 12 of twelve (2.2e-3 to 6.1e-3, then 7.1e-4 to 8.6e-3); weights
rounded to e4m3 were refused on 12 of twelve both times (3.8e-4 to
2.6e-3) and to e5m2 on 12 (6.1e-4 to 6.3e-3), and the program as it is
read 9.1e-6 to 2.3e-4 over forty readings. The counts at five other
ranges are in ``benchmark/configs/deepseek-v2-lite.json``
(``initializer_range_why``): at the class default, 0.02, ``rope_off``
read under the tolerance on one seed of twelve and two of sixteen. The
check reads two sequences, so one ``"correct": true`` from a control
(``scale_plain`` first) is no alarm; several are (PERF.md section 7
(0000)).

No control breaks the router: ``renorm_on`` (the chosen experts'
weights divided by their sum, where the configuration states
``norm_topk_prob`` false) was tried and stays out, refused on 0
of twelve seeds at 0.06 (4.6e-6 to 2.1e-4) and on 6 of twelve at most
at any range tried: this chip's share routes an eighth of the (token,
choice) pairs beside two shared experts that every token passes, and
a control that passes is no control. ``tests/test_deepseek_v2.py``
holds the router's weights, the balance term and the eight shares to
the reference on the CPU.

The program has no switch for any of these: each traces the program's
loss on a configuration that states another model, puts the identity
in the rotation's place while the loss is traced, or zeroes the leaves
before the loss reads them.
"""

from __future__ import annotations

import dataclasses
import functools

# Each refused by ``correct`` on at least eleven of twelve seeds on the
# chip (the counts are above).
NAMES = ("rope_off", "scale_plain", "no_shared")


def broken(name: str, loss):
    """``loss`` (params, tokens, targets), a partial of the program's
    loss on its configuration, with the path ``name`` says broken."""
    from benchmark.controls.kimi_linear import _in_place_of, _without_shared
    from dlrover_tpu.models import llama

    if name not in NAMES:
        raise ValueError(f"no control {name!r}: one of {NAMES}")
    if name == "scale_plain":
        plain = dataclasses.replace(
            loss.keywords["cfg"], mscale=0.0, mscale_all_dim=0.0
        )
        return functools.partial(loss.func, **{**loss.keywords, "cfg": plain})
    if name == "no_shared":
        return lambda params, *batch: loss(_without_shared(params), *batch)

    def traced_unrotated(*args):
        with _in_place_of(llama, "apply_rope", lambda x, cos, sin: x):
            return loss(*args)

    return traced_unrotated
