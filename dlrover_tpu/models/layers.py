"""A stack of identical layers: the first few in line, the rest scanned.

``lax.scan`` over stacked parameters compiles one block whatever the
depth, and charges for it every step: what a layer keeps for its
backward is written into an ``[n_layer, ...]`` stack and sliced out
again, and a Pallas call cannot read a slice in place, so each slice
is a copy at a third of the chip's bandwidth (scope ``layers`` with
no layer scope beneath: ``layer_scan_ms_per_step.train``). A layer
that runs in line pays none of that and puts one more copy of the
block into the executable, which every start reads back. So the first
:data:`IN_LINE` layers run in line and the rest stay scanned.

The first and not the last: a layer's weight gradients are summed
into the stacked leaf's rows beside the scan's, which exist when the
backward scan has run. The first layers' backward comes after it, so
their weight gradients are formed where they are used. The last
layers' backward comes before it, and the chip's scheduler, which
places a product as late as its user lets it, then holds what the
products read (a layer's recomputed values, 0.4 GB a layer at GPT-2
124M's shapes) across the whole backward scan: ``memory_analysis()``
of GPT-2's step read 8.13 GB for the all-scanned stack's 6.46 with
the last three in line, and reads 6.22 with the first three.

:func:`run` is the stack of ``models/gpt.py`` (and through
``gpt.backbone`` of ``models/bert.py``) and of ``models/llama.py``
(and through ``llama.backbone_with_aux`` of ``models/glm.py``); all
four adapt through the one thing the code sees, the stack's length.
``models/ouro.py``, ``models/granite_hybrid.py`` and ``models/mellum.py``
keep their own scans: Ouro's stack runs ``ut_steps`` times a step (all
in line: that many copies in an executable that compiles for 18 s,
+2.25%); Granite's and Mellum's unit is a period of two kinds of layer.
``models/kimi_linear.py`` and ``models/deepseek_v2.py`` keep rows of
calls: five or six layers of two or three kinds, a subtree a layer.
``models/phi4_flash.py`` runs each of its runs of equal units through
:func:`run`, a unit a pair of layers; what the later layers read of
two earlier ones enters the unit as a closed-over constant.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

from dlrover_tpu import obs

# How many layers, counted from the start of the stack, run in line.
# Each puts one more copy of the block into the executable (11.5 MB
# at GPT-2 124M's widths, 22 MB a chip at Mistral-7B's on ``fsdp=4``)
# which a start reads back, and the first one beside a scan costs a
# second lowering of the block (0.7 s). All twelve of GPT-2's in line
# read +9.14% tokens/s for +12.6% ``setup_s`` (chip runs, PR 46).
# Chip runs of PR 51 (PERF.md section 6), on a v5e, with the LAST
# layers in line: four, GPT-2 119,434 -> 123,131 tokens/s and
# ``setup_s`` +5.2% over six warm pairs, Mistral-7B's eight layers on
# four chips +0.95% and +8.0%; three, GPT-2 123,110 (+3.08%: the
# fourth bought nothing) and +4.0%, four chips +1.19% and +6.0%.
# With the FIRST three, as here: GPT-2 121,699 (+1.90%: an exposed
# copy of the embedding table and a later MLP backward give 1.7 ms
# back) and ``setup_s`` +7% over two warm pairs, four chips +1.23%
# and +8.0% (one pair), for the memory the module's docstring gives.
# Mistral-7B's two layers on one chip run in line whichever: 33,784
# -> 34,977 (+3.53%). Not an option: it adapts to the one thing the
# code sees, the stack's length.
IN_LINE = 3


def run(layer: Callable, carry: Any, blocks: Any, unroll: int = 1) -> Any:
    """``carry`` through the ``n`` layers whose parameters ``blocks``
    stacks on its leaves' leading axis, in order: ``layer(carry, lp)
    -> carry`` with ``lp`` one layer's slice.

    The first ``min(n, IN_LINE)`` layers run as calls in a row on
    static slices of ``blocks``, the others under ``lax.scan``
    (``unroll`` is the scan's): what an in-line layer keeps for its
    backward is an ordinary value, never stacked and never sliced, and
    autodiff lays the in-line layers' weight gradients before the
    scan's stacked ones (a pad each, fused into one sum), so the
    gradient has ``blocks``' own shapes. A static slice of a stacked parameter
    is no view on the chip where a product reads it: XLA copies each
    stacked weight out into its layers' once a step, as the scan's
    dynamic slices did (Mistral-7B's two layers: 2.86 ms, all that is
    left of the stack's own time there). ``layer`` is one ``jax.jit``,
    made here, once a trace: the scan's body and every in-line call
    are the same traced function, lowered once for the scan and once
    for the calls. Event ``layers.in_line`` (``in_line``, ``scanned``,
    ``n_layer``) says once a trace how the stack ran.
    """
    n = jax.tree.leaves(blocks)[0].shape[0]
    in_line = min(n, IN_LINE)
    scanned = n - in_line
    layer = jax.jit(layer)
    for i in range(in_line):
        carry = layer(carry, jax.tree.map(lambda a: a[i], blocks))
    if scanned:
        carry, _ = jax.lax.scan(
            lambda c, lp: (layer(c, lp), None), carry,
            jax.tree.map(lambda a: a[in_line:], blocks), unroll=unroll,
        )
    obs.event("layers.in_line", in_line=in_line, scanned=scanned, n_layer=n)
    return carry
