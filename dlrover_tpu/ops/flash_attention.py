"""Flash attention for TPU in Pallas (forward + backward).

Replaces the reference's flash-attn integration — the CUDA wheels and
version-patched modules of atorch/modules/transformer/layers.py:94-182
and the CPU FMHA custom op of tfplus/tfplus/flash_attn/kernels/ — with
one Pallas kernel family designed for the MXU:

* O(T) memory: scores never materialize in HBM; online softmax keeps a
  running (max, sum, acc) per query block in VMEM scratch that persists
  across the sequential kv grid dimension (a sequence of one kv block
  carries nothing and leaves the scratch alone).
* two entries, one set of kernels. :func:`flash_attention` takes
  [batch, seq, heads, head_dim] and hands the kernels [B, H, T, D]
  (grid over batch x heads): a transposition of every operand in
  front of the call and of ``o`` behind it, and on the chip already
  the [B, T, H, D] view of a projection's [B, T, H*D] is a copy of
  the whole array. :func:`flash_attention_wide` takes q [B, T, H*D]
  and k, v [B, T, Hkv*D] where the projections wrote them and returns
  ``o`` where the out-projection reads it: a head is a column block
  of ``D`` lanes (so ``D`` is a multiple of 128: :func:`wide_head_size`),
  query head ``h`` reads key-value head ``h // (H // Hkv)`` through
  the block's index, and nothing is transposed, repeated or viewed on
  either side of the call, forward or backward (``dk`` and ``dv``
  leave the backward kernel a query head each and one small kernel,
  ``flash_group_sum``, adds a group's column slabs). Grouped queries
  are the wide entry's by construction: what ``supports_gqa`` says of
  the sequence-parallel attention functions (compact k and v in, the
  broadcast per block on the device) it has through the index map.
  The kernels' bodies, blocks and names are the same in both
  (:func:`_layout`); ``models/llama.attention_half`` chooses by what
  it can see, the attention function and the head size
  (:func:`wide_form`). Head sizes 64 (two heads a block) and latent
  attention's 192 still take the 4-D entry.
* bf16 inputs feed the 128x128 MXU; all softmax statistics and
  accumulators are float32; the forward's running stats are
  [block_q, 1] columns in VMEM (one lane, not lane-replicated tiles).
  Between the kernels a row statistic (``lse``, ``delta``) is a
  lane-dense row, [B, H, 1, T]: the chip pads a buffer's minor
  dimension to 128 lanes, so a [B, H, T, 1] column takes 128 times its
  bytes. The forward kernel writes ``lse`` as the column its stats
  are, and ``_fwd`` slices it to the row at once.
* causal masking skips fully-masked kv blocks (no MXU work issued) and
  only diagonal-crossing blocks pay for mask generation at all —
  interior blocks run a maskless fast path (softmax bookkeeping is
  VPU-bound; the lower triangle is dominated by interior blocks).
* backward is recompute-based (flash-attn v2 style) but FUSED: one
  kernel computes dq, dk and dv in a single sweep, recomputing p once
  per (kv, q) block pair instead of once per output operand. dk/dv
  accumulate in block scratch; dq accumulates in a full-sequence f32
  VMEM scratch flushed once at the end of each (batch, head) slice
  into a full-sequence output block, which Pallas double-buffers. The
  head dim pads to 128 lanes, so dq costs seq * 128 * (4 + 2*2) bytes
  in bf16: 1 MiB at 1k context, 8 MiB at 8k, 32 MiB at 32k. With the
  per-block operands that passes Mosaic's 16 MiB default scoped-VMEM
  budget between 4k and 8k (the v5e compiler reports 16.04 MiB at 8k,
  D=128), so the backward declares what it needs
  (``_bwd_vmem_limit``) out of the chip's 128 MiB.
  delta = rowsum(dO * O) is precomputed by XLA, as a row like lse. The
  backward holds its score tile key-major (s^T = k q^T), so a
  [1, block_q] row of lse or delta broadcasts down the sublanes as it
  is read and no statistic changes layout inside the loop. A block the
  mask crosses runs as sub-tiles (``_BWD_SPLIT`` a side), and only
  those that hold a live pair: two rolled loops whose bounds are
  integer arithmetic on the block's indices, so the arithmetic is
  traced once more, not once a sub-tile (event ``flash.bwd_area``
  says, once a traced backward, what area a head visits, runs and
  needs). GPT-2's one 1024 x 1024 block runs 3 of its 4 squares.

On non-TPU backends kernels run in interpreter mode so the same code
path is unit-testable on CPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu import obs
from dlrover_tpu.parallel.mesh import per_device, use_interpret

NEG_INF = -1e30


# The chip pads the minor dimension of a buffer in HBM to this many
# lanes.
_LANES = 128

# Mosaic's default scoped-VMEM budget for one kernel on a v5e; a
# kernel that needs more must say so in its compiler parameters.
_DEFAULT_SCOPED_VMEM = 16 << 20


def _bwd_vmem_limit(tq, d, itemsize, block_q, block_k):
    """``vmem_limit_bytes`` for the fused backward, or None while the
    default budget covers it. The kernel holds the whole sequence's
    dq twice over — the f32 accumulator plus the double-buffered
    output block — on top of its per-block operands, so its footprint
    grows with Tq: the v5e compiler reports 16.04 MiB at T=8192,
    D=128 with 1024x1024 blocks, 36 KiB over the default."""
    dpad = -(-d // 128) * 128  # the minor dim pads to the lane width
    dq = tq * dpad * (4 + 2 * itemsize)
    operands = (
        2 * 2 * (block_q + 2 * block_k) * dpad * itemsize  # q do k v dk dv
        + 2 * 2 * 8 * block_q * 4  # lse, delta: one row, sublane-padded
        + 2 * block_k * dpad * 4  # dk/dv accumulators
    )
    spill = 2 * block_q * block_k * 4  # f32 score tiles Mosaic spills
    need = dq + operands + spill
    return need if need > _DEFAULT_SCOPED_VMEM else None


def _block_mask(iq, jk, block_q, block_k, causal, seq_len, pad,
                window, q_offset=0, key_major=False):
    """Mask for block (iq, jk) — only called for blocks that cross the
    diagonal, the sliding-window band edge, or the padding edge;
    interior blocks never generate iotas/compares. ``q_offset``
    (static) shifts q rows to their global positions — the
    rectangular case where q is a chunk of a longer sequence
    (chunked prefill, prefix-LM suffix rows); 0 for square calls.
    [block_q, block_k] as the forward holds its scores, or
    ``key_major`` [block_k, block_q] as the backward does."""
    shape, q_dim, k_dim = (
        ((block_k, block_q), 1, 0) if key_major
        else ((block_q, block_k), 0, 1)
    )
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_dim
    )
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, k_dim
    )
    mask = None
    if pad:
        mask = k_pos < seq_len  # key padding (pad rows contribute 0)
    if causal:
        cm = q_pos >= k_pos
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    if window is not None:
        # Sliding window: query i sees keys (i-window, i] — `window`
        # keys including itself (Mistral convention).
        wm = (q_pos - k_pos) < window
        mask = wm if mask is None else jnp.logical_and(mask, wm)
    return mask


def _dispatch_block(iq, jk, accumulate, *, causal, pad, block_q,
                    block_k, seq_len, window, q_offset=0):
    """Run ``accumulate(masked=...)`` for block (iq, jk), skipping
    fully-future causal blocks and blocks entirely below the sliding
    window band, masking only blocks that cross the diagonal, the
    band edge, or the padding edge — so windowed attention does
    O(T*window) MXU work, not O(T^2). ``q_offset`` shifts q rows to
    global positions (rectangular calls); 0 for square."""
    if not causal and not pad and window is None:
        accumulate(masked=False)
        return
    q0 = q_offset + iq * block_q  # first row's global position
    if causal:
        run = (jk * block_k) <= (q0 + block_q - 1)
        crosses_diag = (jk * block_k + block_k - 1) > q0
    else:
        run = True
        crosses_diag = False
    crosses_pad = ((jk * block_k + block_k) > seq_len) if pad else False
    crosses_band = False
    if window is not None:
        # Lowest visible key for any row in this q block is
        # q0 - window + 1 (the FIRST row's band start); the
        # block is dead when even its last key is below that.
        run = jnp.logical_and(
            run,
            (jk * block_k + block_k - 1) >= (q0 - window + 1),
        )
        # The LAST row's band start is the highest; any key below it
        # needs the element mask.
        crosses_band = (
            (jk * block_k)
            < (q0 + block_q - 1 - window + 1)
        )
    needs_mask = jnp.logical_and(
        run,
        jnp.logical_or(
            jnp.logical_or(crosses_diag, crosses_pad), crosses_band
        ),
    )
    fast = jnp.logical_and(run, jnp.logical_not(needs_mask))

    @pl.when(fast)
    def _fast():
        accumulate(masked=False)

    @pl.when(needs_mask)
    def _masked():
        accumulate(masked=True)


# ---------------------------------------------------------------------------
# The operands' two layouts
# ---------------------------------------------------------------------------


def _layout(q, k, head_dim):
    """Where a head's rows lie in the kernels' operands: ``(batch,
    query heads, query rows, q's and k's head size, q_at, k_at,
    shape)``. ``q_at(b, h, i)`` / ``k_at(b, h, j)`` are the block
    indices of query head ``h``'s row block in a query-side array
    (q, o, do, dq, and dk and dv, which leave the backward a query
    head each) and in a key-side one (k, v); ``shape(t, d)`` is a
    query-side array of ``t`` rows and head size ``d``.

    ``head_dim`` None: ``[B, H, T, D]``, a head a block of dim 1.
    Else the model's own layout under one more unit dim,
    ``[B, 1, T, H*D]`` and ``[B, 1, T, Hkv*D]`` (the projections'
    ``[B, T, H*D]`` with nothing moved): a head is a column block of
    ``head_dim`` lanes, and a query head reads the key-value head of
    its group, so k and v are never written ``H`` wide. The blocks
    have the same rank and sizes either way and the kernels' bodies
    cannot tell."""
    b, h, tq, d = q.shape
    if head_dim is None:
        def at(b, h, t):
            return b, h, t, 0

        return b, h, tq, d, at, at, lambda t, d: (b, h, t, d)
    h, group = d // head_dim, d // k.shape[3]
    return (
        b, h, tq, head_dim,
        lambda b, h, t: (b, 0, t, h),
        lambda b, h, t: (b, 0, t, h // group),
        lambda t, d: (b, 1, t, h * d),
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,      # (1, 1, block_q, d)
    k_ref,      # (1, 1, block_k, d)
    v_ref,
    o_ref,      # (1, 1, block_q, d)
    lse_ref,    # (1, 1, block_q, 1)
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window,
    block_q: int,
    block_k: int,
    num_kv: int,
    seq_len: int,
    pad: bool,
    q_offset: int,
):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    # One kv block with no band to skip it: the block's softmax is the
    # row's, so nothing is carried and the scratch is not touched.
    carried = num_kv > 1 or window is not None

    if carried:
        @pl.when(jk == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def _write(m, l, acc):
        l_safe = jnp.maximum(l, 1e-30)  # fully-masked rows (padding)
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l_safe)  # a column, as the stats are

    def _accumulate(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale != 1.0:  # power-of-2 scales are folded into q outside
            s = s * scale
        if masked:
            mask = _block_mask(
                iq, jk, block_q, block_k, causal, seq_len, pad,
                window, q_offset,
            )
            s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.max(s, axis=1, keepdims=True)  # (block_q, 1)
        if carried:
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp(m_prev - m_new)  # (block_q, 1): 1-lane exps
        p = jnp.exp(s - m_new)
        if masked and (pad or window is not None):
            # Padding — and sliding windows — can leave a row with no
            # unmasked key in an executed block (m_new = NEG_INF ->
            # exp(0) = 1): under a window, a row's band may start in a
            # later kv block than the first one the block-level skip
            # admits for its q block. Under pure causal masking every
            # executed row has a finite m_new, so exp(NEG_INF - m_new)
            # already underflows to exactly 0 and the select is waste.
            p = jnp.where(mask, p, 0.0)
        l_new = jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if carried:
            l_scr[:] = l_scr[:] * alpha + l_new
            m_scr[:] = m_new
            acc_scr[:] = acc_scr[:] * alpha + pv
        else:
            _write(m_new, l_new, pv)

    _dispatch_block(
        iq, jk, _accumulate, causal=causal, pad=pad, block_q=block_q,
        block_k=block_k, seq_len=seq_len, window=window,
        q_offset=q_offset,
    )

    if carried:
        @pl.when(jk == num_kv - 1)
        def _finalize():
            _write(m_scr[:], l_scr[:], acc_scr[:])


def _fwd(q, k, v, causal, window, scale, block_q, block_k, seq_len,
         interpret, q_offset=0, head_dim=None):
    """q: [B, H, Tq, D]; k/v: [B, H, Tk, D] (each padded to its block
    multiple — Tq == Tk for the square call). Returns (o [B,H,Tq,D],
    lse [B,H,1,Tq]). ``seq_len`` is the true KEY length: keys beyond
    it are masked out. ``q_offset`` is the global position of q row 0
    (causal/window comparisons happen in key coordinates). ``v`` may
    have a head size of its own (latent attention's 192 and 128): the
    output has v's, and nothing else in the kernel reads a size.
    With ``head_dim`` the operands and ``o`` are :func:`_layout`'s
    wide ones; ``lse`` is the same row either way."""
    b, h, tq, d, q_at, k_at, shape = _layout(q, k, head_dim)
    tk, dv = k.shape[2], head_dim or v.shape[3]
    num_q = tq // block_q
    num_kv = tk // block_k
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_kv=num_kv,
        seq_len=seq_len,
        pad=seq_len < tk,
        q_offset=q_offset,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, i, j: q_at(b, h, i)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: k_at(b, h, j)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, i, j: k_at(b, h, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b, h, i, j: q_at(b, h, i)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(shape(tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            )
        ),
        interpret=interpret,
        # What the device trace calls the kernel, whatever transform
        # encloses the call (remat, shard_map, a scope).
        name="flash_attention_fwd",
    )(q, k, v)
    # The kernel's column is lane-padded 128 times in HBM; everything
    # after this line holds the rows along the lanes. (A row written
    # by the kernel itself measured 0.5% faster for GPT-2 and 2.1%
    # slower for Granite, whose scan bodies XLA then lays out
    # otherwise: PERF.md, PR 35.)
    return o, lse[..., 0][:, :, None]


# ---------------------------------------------------------------------------
# Backward: one fused kernel for dq, dk, dv
# ---------------------------------------------------------------------------


# Sub-tiles along each side of a backward block that the mask crosses
# (the diagonal, the band's edge, the key padding): the kernel runs
# only those of its n x n sub-tiles that hold a live (query, key)
# pair. 2 is 512 x 512 on the chip's 1024 blocks, 3 of GPT-2's 4.
# Chosen on the chip (PERF.md, PR 39): GPT-2's backward kernel takes
# 15.88 ms a step at 2 and 24.07 at 4 (256 x 256, 10 of 16 sub-tiles),
# 20.79 whole.
_BWD_SPLIT = 2


def _bwd_sub(block_q, block_k, interpret):
    """The side of a crossing backward block's sub-tiles: the blocks'
    common divisor split :data:`_BWD_SPLIT` ways, or fewer where that
    leaves no whole number of lanes (the kernel slices a q block's
    ``lse`` and ``delta`` rows along the lanes; interpreted, any
    multiple of 8 does). The whole block where nothing does."""
    whole = math.gcd(block_q, block_k)
    align = 8 if interpret else _LANES
    n = _BWD_SPLIT
    while n > 1 and (whole % n or (whole // n) % align):
        n //= 2
    return whole // n


def _live_q_tiles(k0, q0, sub, n_q, causal, window, seq_len):
    """[lo, hi): the query sub-tiles b (rows ``q0 + b * sub`` on, in key
    coordinates) that hold a live pair with the keys ``k0`` to
    ``k0 + sub - 1``, of which only those below ``seq_len`` are real
    (None: all). The differences row - key over such a tile are every
    whole number between their extremes, so the tile is live when that
    range meets [0, window). On Python ints (:func:`bwd_area`) and on
    the kernel's traced scalars alike."""
    ints = isinstance(k0, int) and isinstance(q0, int)
    most, least = (max, min) if ints else (jnp.maximum, jnp.minimum)
    k_end = k0 + sub if seq_len is None else least(k0 + sub, seq_len)
    lo, hi = 0, n_q
    if causal:  # the tile's last row is at or after its first key
        lo = most(k0 - q0, 0) // sub
    if window is not None:  # its first row sees its last real key
        hi = least(hi, most(k_end - 1 + window - 1 - q0 + sub, 0) // sub)
    if seq_len is not None:  # no real key, no tile
        hi = least(hi, most(seq_len - k0, 0) * n_q)
    return lo, hi


def _bwd_blocks(tq, tk, block_q, block_k, sub, causal, window, seq_len,
                q_offset=0):
    """What one head of the backward kernel executes, from its static
    arguments: ``(iq, jk, tiles)`` for every block ``_dispatch_block``
    runs, ``tiles`` None where it runs whole and unmasked, else the
    live sub-tiles ``(b, a)`` (query, key) of a block the mask
    crosses."""
    for iq in range(tq // block_q):
        q0 = q_offset + iq * block_q
        for jk in range(tk // block_k):
            k0 = jk * block_k
            # _dispatch_block's conditions, on ints.
            runs = not causal or k0 <= q0 + block_q - 1
            crosses = causal and k0 + block_k - 1 > q0
            crosses = crosses or k0 + block_k > seq_len
            if window is not None:
                runs = runs and k0 + block_k - 1 >= q0 - window + 1
                crosses = crosses or k0 < q0 + block_q - window
            if not runs:
                continue
            if not crosses:
                yield iq, jk, None
                continue
            tiles = []
            for a in range(block_k // sub):
                lo, hi = _live_q_tiles(
                    k0 + a * sub, q0, sub, block_q // sub, causal,
                    window, seq_len if seq_len < tk else None,
                )
                tiles += [(b, a) for b in range(lo, hi)]
            yield iq, jk, tiles


def bwd_area(tq, tk, block_q, block_k, sub, causal, window, seq_len,
             q_offset=0):
    """(query, key) pairs a head of one backward call: ``visited``,
    the area of the blocks the kernel executes (all of it computed
    before the sub-tiles); ``run``, what it computes: a block the
    mask does not cross whole, a crossing block's live sub-tiles;
    ``required``, the pairs the mask admits (a square causal call:
    ``t * (t + 1) / 2``). ``tq`` and ``tk`` are the padded lengths,
    ``seq_len`` the true key length."""
    visited = run = 0
    for _, _, tiles in _bwd_blocks(
        tq, tk, block_q, block_k, sub, causal, window, seq_len, q_offset
    ):
        visited += block_q * block_k
        run += block_q * block_k if tiles is None else len(tiles) * sub * sub
    required = 0
    for row in range(q_offset, q_offset + tq):
        last = min(row, seq_len - 1) if causal else seq_len - 1
        first = max(row - window + 1, 0) if window is not None else 0
        required += max(last - first + 1, 0)
    return {"visited": visited, "run": run, "required": required}


def _bwd_kernel(
    q_ref,      # (1, 1, block_q, d)
    k_ref,      # (1, 1, block_k, d)
    v_ref,
    do_ref,     # (1, 1, block_q, d)
    lse_ref,    # (1, 1, 1, block_q)
    delta_ref,  # (1, 1, 1, block_q)
    dq_ref,     # (1, 1, t, d) — whole-sequence block, written once
    dk_ref,     # (1, 1, block_k, d)
    dv_ref,
    dq_scr,     # (t, d) f32 — full-sequence accumulator
    dk_scr,
    dv_scr,
    *,
    scale: float,
    causal: bool,
    window,
    block_q: int,
    block_k: int,
    num_q: int,
    num_kv: int,
    seq_len: int,
    pad: bool,
    q_offset: int,
    sub: int,
):
    jk = pl.program_id(2)  # kv block (outer)
    iq = pl.program_id(3)  # q block (inner)

    @pl.when(jnp.logical_and(jk == 0, iq == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(iq == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)

    def _accumulate(rows, cols, mask):
        """dQ, dK and dV gain what the q rows ``rows`` and the keys
        ``cols`` of this block give (``pl.ds`` slices; ``mask`` is the
        [keys, rows] element mask, or None where every pair is live)."""
        q = q_ref[0, 0, rows, :]
        k = k_ref[0, 0, cols, :]
        v = v_ref[0, 0, cols, :]
        do = do_ref[0, 0, rows, :]
        # Every tile below is key-major, [keys, rows]: the [1, rows]
        # rows of lse and delta broadcast down the sublanes, and dV
        # and dK are plain products.
        # S^T = K Q^T
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale != 1.0:
            st = st * scale
        pt = jnp.exp(st - lse_ref[0, 0, :, rows])
        if mask is not None:
            pt = jnp.where(mask, pt, 0.0)
        # dV += P^T dO
        dv_scr[cols, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dP^T = V dO^T ; dS^T = P^T * (dP^T - delta) * scale
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # delta folds BOTH cotangents: rowsum(dO*O) from the output
        # and -g_lse from the logsumexp (dlse/ds_j = p_j), see _bwd.
        dst = pt * (dpt - delta_ref[0, 0, :, rows])
        if scale != 1.0:
            dst = dst * scale
        dst = dst.astype(q.dtype)
        # dK += dS^T Q
        dk_scr[cols, :] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dQ[iq] += dS K, the one product that contracts over the
        # tiles' leading dimension — accumulated across the outer kv
        # loop in the full-sequence scratch (no second recompute pass).
        sl = pl.ds(iq * block_q + rows.start, rows.size)
        dq_scr[sl, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _tile_mask(b, a):
        # Sub-tile (b, a) of this block is block (iq * n + b, jk * n + a)
        # of a grid of ``sub`` x ``sub`` blocks.
        return _block_mask(
            iq * (block_q // sub) + b, jk * (block_k // sub) + a, sub,
            sub, causal, seq_len, pad, window, q_offset, key_major=True,
        )

    def _crossing():
        """A block the mask crosses: only its live sub-tiles, in two
        rolled loops, so the arithmetic is traced once however many
        sub-tiles a block has."""
        if sub == block_q == block_k:
            _accumulate(whole_q, whole_k, _tile_mask(0, 0))
            return
        q0 = q_offset + iq * block_q

        def key_tiles(a, carry):
            k0 = jk * block_k + a * sub
            lo, hi = _live_q_tiles(
                k0, q0, sub, block_q // sub, causal, window,
                seq_len if pad else None,
            )

            def query_tiles(b, carry):
                # Masked whether or not the diagonal crosses this one:
                # a second, maskless copy of the arithmetic for the
                # sub-tiles that are wholly live read the same time.
                _accumulate(
                    pl.ds(pl.multiple_of(b * sub, sub), sub),
                    pl.ds(pl.multiple_of(a * sub, sub), sub),
                    _tile_mask(b, a),
                )
                return carry

            return jax.lax.fori_loop(lo, hi, query_tiles, carry)

        jax.lax.fori_loop(0, block_k // sub, key_tiles, 0)

    def _block(masked: bool):
        if masked:
            _crossing()
        else:
            _accumulate(whole_q, whole_k, None)

    _dispatch_block(
        iq, jk, _block, causal=causal, pad=pad, block_q=block_q,
        block_k=block_k, seq_len=seq_len, window=window,
        q_offset=q_offset,
    )

    @pl.when(iq == num_q - 1)
    def _flush_dkv():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(jk == num_kv - 1, iq == num_q - 1))
    def _flush_dq():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(
    q, k, v, o, lse, do, causal, window, scale, block_q, block_k,
    seq_len, interpret, g_lse=None, q_offset=0, head_dim=None,
):
    b, h, tq, d, q_at, k_at, shape = _layout(q, k, head_dim)
    # v, o and do have v's head size
    tk, dv = k.shape[2], head_dim or v.shape[3]
    num_q = tq // block_q
    num_kv = tk // block_k
    pad = seq_len < tk
    sub = _bwd_sub(block_q, block_k, interpret)
    obs.event(
        "flash.bwd_area", t=seq_len, block_q=block_q, block_k=block_k,
        sub=sub, window=window,
        **bwd_area(tq, tk, block_q, block_k, sub, causal, window,
                   seq_len, q_offset),
    )
    # [B, H, 1, T] like lse; XLA fuses this rowsum
    delta = _head_sums(do, o, head_dim)[:, :, None]
    if g_lse is not None:
        # lse cotangent: dlse/ds_j = p_j, so dS gains p * g_lse — the
        # same rank-1 shape as the delta term, folded in host-side.
        delta = delta - g_lse

    kernel = functools.partial(
        _bwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_q=num_q,
        num_kv=num_kv,
        seq_len=seq_len,
        pad=pad,
        q_offset=q_offset,
        sub=sub,
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b, h, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, j, i: q_at(b, h, i)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, j, i: k_at(b, h, j)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, j, i: k_at(b, h, j)),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b, h, j, i: q_at(b, h, i)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, j, i: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, j, i: (b, h, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda b, h, j, i: q_at(b, h, 0)),
            # dk and dv a query head each, where the head's q lies.
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, j, i: q_at(b, h, j)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, j, i: q_at(b, h, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(shape(tq, d), q.dtype),
            jax.ShapeDtypeStruct(shape(tk, d), k.dtype),
            jax.ShapeDtypeStruct(shape(tk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary"
            ),
            vmem_limit_bytes=_bwd_vmem_limit(
                tq, max(d, dv), q.dtype.itemsize, block_q, block_k
            ),
        ),
        interpret=interpret,
        # What the device trace calls the kernel, whatever transform
        # encloses the call (remat, shard_map, a scope).
        name="flash_attention_bwd",
    )(q, k, v, do, lse, delta)
    if head_dim is None:
        return dq, dk, dv
    group = q.shape[3] // k.shape[3]
    return (dq, *_group_sums((dk, dv), group, d, interpret))


def _head_sums(do, o, head_dim):
    """``delta``'s rows ``[B, H, T]``: the sum of ``do * o`` (float32)
    over each head's columns. The operands ``[B, H, T, D]``, or with
    ``head_dim`` the wide ``[B, 1, T, H*D]``: there a head's sum is a
    product with the heads' constant 0/1 membership ``[H*D, H]``
    (``models/kimi_linear._per_head``'s way), float32 at precision
    ``highest``, which XLA fuses with the multiply into one pass over
    ``do`` and ``o`` where they lie; a ``[B, T, H, D]`` view of such an
    array is on the chip a copy of all of it, and slices a head are a
    fusion a head."""
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if head_dim is None:
        return jnp.sum(prod, axis=-1)
    e = prod.shape[3]
    member = (
        jnp.arange(e)[:, None] // head_dim == jnp.arange(e // head_dim)
    ).astype(jnp.float32)
    return jnp.einsum(
        "bte,eh->bht", prod[:, 0], member,
        precision=jax.lax.Precision.HIGHEST,
    )


def row_block(t: int) -> int:
    """Rows a grid step of a kernel that streams an array once
    (``flash_group_sum``, ``ops/rope.rope_wide``): the largest power
    of two up to 1024 that divides ``t``, or all of them."""
    return next((r for r in (1024, 512, 256, 128, 64, 32, 16, 8)
                 if t % r == 0), t)


def _group_sum_kernel(*refs, group, d):
    """Each input block holds a key-value head's ``group`` query
    heads' shares side by side: their sum, float32, rounded once."""
    n = len(refs) // 2
    for x_ref, y_ref in zip(refs[:n], refs[n:]):
        total = x_ref[0, 0, :, pl.ds(0, d)].astype(jnp.float32)
        for r in range(1, group):
            total += x_ref[0, 0, :, pl.ds(r * d, d)].astype(jnp.float32)
        y_ref[0, 0] = total.astype(y_ref.dtype)


def _group_sums(xs, group, d, interpret):
    """The wide backward's ``dk`` and ``dv``, each ``[B, 1, T, H*d]``
    with a query head's share in the head's columns, summed over each
    key-value head's ``group`` query heads: ``[B, 1, T, Hkv*d]``. A
    group's shares lie side by side, slabs of whole lanes, so one
    kernel reads each array once and writes it ``Hkv`` wide (the sum
    in float32, rounded once, as XLA sums a repeat's cotangent).
    Written as slices and a ``concatenate`` XLA makes a fusion a
    key-value head of it and a pass that joins them."""
    if group == 1:
        return xs
    b, _, t, e = xs[0].shape
    rows = row_block(t)
    return pl.pallas_call(
        functools.partial(_group_sum_kernel, group=group, d=d),
        grid=(b, t // rows, e // (group * d)),
        in_specs=[
            pl.BlockSpec((1, 1, rows, group * d),
                         lambda b, i, g: (b, 0, i, g))
        ] * len(xs),
        out_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda b, i, g: (b, 0, i, g))
        ] * len(xs),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, t, e // group), x.dtype)
            for x in xs
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name="flash_group_sum",
    )(*xs)


# ---------------------------------------------------------------------------
# custom_vjp plumbing, on either layout (``head_dim``: :func:`_layout`)
# ---------------------------------------------------------------------------


def _kept(o, lse):
    """The flash forward's outputs as ``remat="full"`` keeps them
    (accelerate/remat.py names them), and the kernel's layout of ``o``
    back from that: (o [B,H,T,D], kept o, kept lse).

    ``lse`` is kept as ``_fwd`` returns it and the backward kernel
    reads it, ``[B, H, 1, T]`` with the rows along the lanes (0.9 MB
    a layer at GPT-2's shape; a ``[B, H, T, 1]`` column is padded to
    113). ``o`` is kept as
    the kernel wrote it where the head size fills the lanes (on the
    wide entry that is ``[B, 1, T, H*D]``, the model's own layout:
    :func:`_kernel_layout` has nothing to do there); at a
    smaller head size ``[B, H, T, D]`` is padded too (twice the bytes
    at 64: 0.34 GB of GPT-2's step, and slower than the transposition
    it saves), so there it is kept in the model's layout
    ``[B, T, H*D]``, which is not."""
    from dlrover_tpu.accelerate.remat import FLASH_LSE, FLASH_O, keep

    kept_lse = keep(lse, FLASH_LSE)
    b, h, t, d = o.shape
    if d % _LANES == 0:
        kept_o = keep(o, FLASH_O)
        return kept_o, kept_o, kept_lse
    kept_o = keep(o.transpose(0, 2, 1, 3).reshape(b, t, h * d), FLASH_O)
    return _kernel_layout(kept_o, h), kept_o, kept_lse


def _kernel_layout(kept_o, h):
    """``[B, H, T, D]`` from either layout :func:`_kept` keeps."""
    if kept_o.ndim == 4:
        return kept_o
    b, t, e = kept_o.shape
    return kept_o.reshape(b, t, h, e // h).transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13)
)
def _flash(q, k, v, causal, window, scale, block_q, block_k,
           block_q_bwd, block_k_bwd, seq_len, interpret, q_offset=0,
           head_dim=None):
    o, lse = _fwd(q, k, v, causal, window, scale, block_q, block_k,
                  seq_len, interpret, q_offset, head_dim)
    # Named here too: the forward rule is traced only later, under
    # differentiation, and the ``remat.kept`` event reads the names
    # while the block is traced.
    return _kept(o, lse)[0]


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k,
               block_q_bwd, block_k_bwd, seq_len, interpret,
               q_offset=0, head_dim=None):
    o, lse = _fwd(
        q, k, v, causal, window, scale, block_q, block_k, seq_len,
        interpret, q_offset, head_dim
    )
    # The primal output and the residuals are the kept values, so a
    # block under remat="full" hands them to the backward as they are
    # and does not run the forward kernel again.
    o, kept_o, kept_lse = _kept(o, lse)
    return o, (q, k, v, kept_o, kept_lse)


def _flash_bwd(causal, window, scale, block_q, block_k, block_q_bwd,
               block_k_bwd, seq_len, interpret, q_offset, head_dim,
               res, g):
    q, k, v, kept_o, kept_lse = res
    return _bwd(
        q, k, v, _kernel_layout(kept_o, q.shape[1]), kept_lse, g,
        causal, window, scale, block_q_bwd, block_k_bwd, seq_len,
        interpret, q_offset=q_offset, head_dim=head_dim,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13)
)
def _flash_lse(q, k, v, causal, window, scale, block_q, block_k,
               block_q_bwd, block_k_bwd, seq_len, interpret,
               q_offset=0, head_dim=None):
    """Like _flash but also returns the per-row logsumexp — the
    ingredient ring attention needs to merge normalized block outputs
    across devices (parallel/ring_attention.py)."""
    return _fwd(
        q, k, v, causal, window, scale, block_q, block_k, seq_len,
        interpret, q_offset, head_dim
    )


def _flash_lse_fwd(q, k, v, causal, window, scale, block_q, block_k,
                   block_q_bwd, block_k_bwd, seq_len, interpret,
                   q_offset=0, head_dim=None):
    o, lse = _fwd(
        q, k, v, causal, window, scale, block_q, block_k, seq_len,
        interpret, q_offset, head_dim
    )
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, window, scale, block_q, block_k,
                   block_q_bwd, block_k_bwd, seq_len, interpret,
                   q_offset, head_dim, res, g):
    g_o, g_lse = g
    q, k, v, o, lse = res
    return _bwd(
        q, k, v, o, lse, g_o, causal, window, scale, block_q_bwd,
        block_k_bwd, seq_len, interpret, g_lse=g_lse,
        q_offset=q_offset, head_dim=head_dim,
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _per_device_flash(flash, q, k, v, static, head_dim=None):
    """``_flash``/``_flash_lse`` on [B, H, T, D] operands, batch rows
    and heads split over the ambient mesh (:func:`per_device`). The
    whole custom_vjp sits inside the shard_map, so the backward
    kernel is split with it. With ``head_dim`` the operands are the
    wide ones, ``[B, 1, T, H*D]`` and ``[B, 1, T, Hkv*D]``: the heads
    are then split along the columns, whole heads of q and of k and v
    to a device, and a device's query heads find their groups'
    key-value heads among its own."""
    if head_dim is None:
        return per_device(
            lambda q, k, v: flash(q, k, v, *static),
            q, k, v, split=(True, True, True), heads_dim=1,
        )
    return per_device(
        lambda q, k, v: flash(q, k, v, *static, 0, head_dim),
        q, k, v, split=(True, True, True), heads_dim=3,
        head_size=head_dim,
        # ``lse`` is a row a head, [B, H, 1, T], in either layout.
        out_heads_dims=(3, 1) if flash is _flash_lse else None,
    )


def _check_block_chain(blocks, t: int) -> int:
    """lcm of ``blocks``, rejecting sets whose combined lcm would
    materially inflate the padded sequence. Divisibility-chain-ish
    sets (lcm <= 2*max) always pass; a coprime set passes only when
    the padding it actually forces at this ``t`` stays under one
    max-block of slack — so tuned configs where t already divides the
    lcm keep working, while e.g. bq=512/bqb=384 at t=520 (pad to
    1536, ~3x kernel work) are rejected."""
    lcm = math.lcm(*blocks)
    if lcm > 2 * max(blocks) and (-t) % lcm >= max(blocks):
        raise ValueError(
            f"block sizes {tuple(blocks)} are too coprime at t={t}: "
            f"padding to their lcm ({lcm}) would inflate the "
            "sequence for every kernel, not just the one being tuned "
            "— pick sizes that divide one another"
        )
    return lcm


def default_block_sizes(t: int) -> tuple:
    """(block_q, block_k) by sequence length, measured on a v5e: 512
    blocks beat 128 by ~2.5x at T=1024 (fewer grid steps, less
    per-block softmax bookkeeping), and 1024 x 1024 beats 512 from
    4k context up. PR 39 read the backward at both on PR 35's
    key-major body, kernel alone (ms a call, 1024 against 512
    backward blocks): Mistral's T=8192 with window 4096 8.16 against
    9.12, OLMoE's T=4096 5.32 against 5.80, Granite's 2.95 against
    3.17; GPT-2's T=1024, one block that is all diagonal, is the
    exception, 2.58 against 2.38 and in the 18 x 1024 step
    ``flash_bwd_ms_per_step.train`` 20.79 against 19.41, 115,766
    against 116,753 tokens/s, because 512 blocks skip the dead
    quarter. The backward now runs a crossing block as 512 x 512
    sub-tiles and skips the dead ones itself (15.88 ms a step for
    GPT-2), which smaller blocks with their own sub-tiles do not
    beat (512 blocks, 256 sub-tiles: 2.71 ms a call against 2.17).
    The f32 score tile is
    [block_q, block_k] (4 MB at 1024x1024), VMEM-safe alongside the
    q/k/v/o blocks at head dims up to 128. Below 1024 context the
    block covers the sequence; block_k doubles only when the
    sequence is a multiple of 2*block_q — otherwise unequal blocks
    would pad to lcm(block_q, block_k), which explodes for lengths
    like 520 (lcm(512, 520) = 33280)."""
    if t % 1024 == 0:
        # The measured optimum — only where it costs no padding
        # (t=1536 would pad to 2048, +33% kernel work; t=516 would
        # yield a sublane-misaligned 516 block).
        return 1024, 1024
    bq = max(min(512, t), 8)
    bk = 2 * bq if t % (2 * bq) == 0 else bq
    return bq, bk


def _square_plan(t, d, causal, window, scale, block_q, block_k,
                 block_q_bwd, block_k_bwd):
    """What a square call settles before it touches an operand, for
    ``t`` tokens and heads of ``d``: ``(window, fold, scale, blocks,
    pad)``. ``window`` checked, and None where the band covers the
    sequence; ``fold`` a factor to multiply q by outside the kernel
    (else None) and ``scale`` what the kernel then applies; ``blocks``
    the four block sizes; ``pad`` the rows that bring ``t`` to a
    multiple of every block."""
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= t:
            window = None  # band covers the whole sequence: plain causal
    if scale is None:
        scale = 1.0 / (d**0.5)
    # Power-of-2 scales (every power-of-4 head_dim, e.g. 64 -> 1/8)
    # multiply exactly in any float dtype, so fold them into q outside
    # the kernel: XLA fuses the multiply into the surrounding
    # transpose/pad, the kernel's `s * scale` pass over each
    # [block_q, block_k] tile disappears (scale==1.0 folds at trace
    # time), and autodiff routes the q-gradient scale through this
    # multiply.
    fold = None
    if scale != 1.0 and math.frexp(scale)[0] == 0.5:
        fold, scale = scale, 1.0
    # A requested block larger than the sequence means "one tile
    # spanning the whole (padded) sequence". Clamp those to the padded
    # length implied by the in-range blocks — that adds no padding and
    # always satisfies the divisibility-chain guard below, unlike
    # clamping to t itself (block_k=1024 at t=520 -> 520 used to trip
    # the guard for a call that tuned fine at longer sequences).
    cap = max(t, 8)
    dq_, dk_ = default_block_sizes(t)
    req_q = dq_ if block_q is None else block_q
    req_k = dk_ if block_k is None else block_k
    req_qb = req_q if block_q_bwd is None else block_q_bwd
    req_kb = req_k if block_k_bwd is None else block_k_bwd
    reqs = (req_q, req_k, req_qb, req_kb)
    in_range = [r for r in reqs if r <= cap]
    # Guard the in-range blocks BEFORE substituting padded_base (a
    # multiple of their lcm): the substitution makes padded_base the
    # max of the final block set, so the post-substitution check alone
    # can never fire for coprime in-range blocks — e.g. bq=512,
    # bqb=384, bk=1024 at t=520 must be rejected, not silently padded
    # 520 -> 1536 (~3x kernel work).
    unit = _check_block_chain(in_range, t) if in_range else 1
    padded_base = max(8, math.ceil(t / unit) * unit)
    blocks = tuple(r if r <= cap else padded_base for r in reqs)
    # Pad so the padded length is divisible by EVERY block size (lcm),
    # otherwise the floor-divided grids would silently drop tail
    # blocks. Inflation protection lives entirely in the
    # pre-substitution check above: after substitution padded_base is
    # a multiple of lcm(in_range) and the max of the set, so this lcm
    # equals padded_base (or lcm(in_range) when nothing was
    # substituted) and cannot explode.
    return window, fold, scale, blocks, (-t) % math.lcm(*blocks)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    window: Optional[int] = None,
) -> "jax.Array | tuple[jax.Array, jax.Array]":
    """Flash attention on [batch, seq, heads, head_dim] inputs.

    Drop-in for models.gpt._default_attention. The [B,H,T,D] kernel
    layout transposes sit OUTSIDE the pallas_call so XLA can fuse them
    into the neighbouring projection matmuls. Pads seq to a block
    multiple internally (padded keys are masked, padded query rows are
    sliced off). Runs interpreted off-TPU so tests exercise the same
    kernel on CPU.

    ``return_lse=True`` also returns the per-row logsumexp [B, H, T]
    (f32, differentiable) — used by ring attention to merge block
    outputs across devices.

    ``block_q_bwd``/``block_k_bwd`` tune the backward kernel's blocks
    independently of the forward's (they default to the forward
    blocks); the backward's access pattern (kv-outer grid, dq
    full-sequence scratch) can favor different tiles. The backward
    reads a q block's ``lse`` and ``delta`` along the lanes, so on the
    chip its q block is a multiple of 128 rows or the whole padded
    sequence (Mosaic's block rule; the defaults are).

    ``window`` enables Mistral-style sliding-window attention: query
    i attends to keys (i-window, i], and kv blocks entirely below the
    band are skipped — O(T*window) MXU work instead of O(T^2).
    Requires ``causal=True``.

    ``v`` may have a head size of its own (latent attention: queries
    and keys of 192 columns, values of 128): the output has v's, the
    default scale is one over the root of q's, and the kernels size
    each block by the array it is of and adapt on nothing else.
    """
    if interpret is None:
        interpret = use_interpret()
    b, t, h, d = q.shape
    window, fold, scale, blocks, pad = _square_plan(
        t, d, causal, window, scale, block_q, block_k, block_q_bwd,
        block_k_bwd,
    )
    if fold is not None:
        q = q * jnp.asarray(fold, q.dtype)

    def to_kernel_layout(x):
        x = jnp.transpose(x, (0, 2, 1, 3))  # [B,H,T,D]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x

    qk, kk, vk = map(to_kernel_layout, (q, k, v))
    static = (causal, window, scale, *blocks, t, interpret)
    if return_lse:
        o, lse = _per_device_flash(_flash_lse, qk, kk, vk, static)
        o = o[:, :, :t].transpose(0, 2, 1, 3)
        return o.astype(q.dtype), lse[:, :, 0, :t]
    o = _per_device_flash(_flash, qk, kk, vk, static)
    o = o[:, :, :t].transpose(0, 2, 1, 3)
    return o.astype(q.dtype)


def flash_attention_wide(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    n_head: int,
    n_kv_head: Optional[int] = None,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    window: Optional[int] = None,
) -> "jax.Array | tuple[jax.Array, jax.Array]":
    """:func:`flash_attention` on the layout the projections write and
    the out-projection reads: q ``[B, T, H*D]``, k and v
    ``[B, T, Hkv*D]``, the result ``[B, T, H*D]`` (with
    ``return_lse`` also ``[B, H, T]``, as there).

    The same kernels, bodies and blocks; only where a block lies
    differs (:func:`_layout`): a head is a column block of ``D``
    lanes of the array as it is, so ``D`` is a multiple of 128
    (:func:`wide_head_size` asks), and nothing is transposed into or
    out of a kernel's layout on either side of the call, forward or
    backward. Grouped queries are this entry's by construction: query
    head ``h`` reads key-value head ``h // (H // Hkv)`` through the
    block's index, k and v are never repeated to the query heads, and
    ``dk`` and ``dv`` come back ``Hkv`` wide (the kernel writes a
    query head's share each; a group's shares are summed in one
    fused add of column slabs). ``remat="full"`` keeps ``o`` as the
    kernel wrote it, which is the model's layout. Event
    ``flash.wide`` says, once a traced call, that this entry ran."""
    if interpret is None:
        interpret = use_interpret()
    b, t, e = q.shape
    n_kv_head = n_kv_head or n_head
    d = e // n_head
    if not wide_head_size(d) or e != n_head * d or n_head % n_kv_head:
        raise ValueError(
            f"flash_attention_wide reads a head as a block of whole "
            f"lanes: {n_head} heads of {e} columns over {n_kv_head} "
            f"key-value heads are not heads of a multiple of {_LANES}"
        )
    if k.shape != (b, t, n_kv_head * d) or v.shape != k.shape:
        raise ValueError(
            f"k {k.shape} and v {v.shape} are not [B, T, Hkv*D] = "
            f"{(b, t, n_kv_head * d)}"
        )
    obs.event("flash.wide", heads=n_head, kv_heads=n_kv_head, head_dim=d)
    window, fold, scale, blocks, pad = _square_plan(
        t, d, causal, window, scale, block_q, block_k, block_q_bwd,
        block_k_bwd,
    )
    if fold is not None:
        q = q * jnp.asarray(fold, q.dtype)

    def to_kernel_layout(x):
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x[:, None]  # one more unit dim: nothing moves

    qk, kk, vk = map(to_kernel_layout, (q, k, v))
    static = (causal, window, scale, *blocks, t, interpret)
    if return_lse:
        o, lse = _per_device_flash(_flash_lse, qk, kk, vk, static, d)
        return o[:, 0, :t].astype(q.dtype), lse[:, :, 0, :t]
    o = _per_device_flash(_flash, qk, kk, vk, static, d)
    return o[:, 0, :t].astype(q.dtype)


def wide_head_size(head_dim: int) -> bool:
    """Whether :func:`flash_attention_wide` can read heads of this
    size where they lie: a column block of ``[B, T, H*D]`` is whole
    lanes. (64, two heads a block, and latent attention's 192 are not
    written yet: such callers keep the ``[B, T, H, D]`` entry.)"""
    return head_dim % _LANES == 0


def wide_form(attn_fn):
    """:func:`flash_attention_wide` with ``attn_fn``'s keywords bound,
    where ``attn_fn`` is :func:`flash_attention` with keywords bound
    (what ``gpt.default_attention_for`` returns and a family binds a
    window to); None for any other attention function, which takes
    ``[B, T, H, D]``."""
    if isinstance(attn_fn, functools.partial) and not attn_fn.args:
        if attn_fn.func is flash_attention:
            return functools.partial(flash_attention_wide, **attn_fn.keywords)
    return None


def flash_attention_rect(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    q_offset: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    interpret: Optional[bool] = None,
    return_lse: bool = False,
    window: Optional[int] = None,
) -> "jax.Array | tuple[jax.Array, jax.Array]":
    """Rectangular flash attention: q [B, Tq, H, D] against
    k/v [B, Tk, H, D] with Tq != Tk allowed.

    ``q_offset`` is the global position of q row 0 in key
    coordinates: causal means q row i attends keys j <= q_offset + i.
    Defaults to ``Tk - Tq`` — "the queries are the LAST Tq positions
    of the key sequence", the chunked-prefill convention (a decode
    chunk attends the whole cache causally). Pass 0 for "queries
    start at key 0".

    Use cases this unlocks at exact cost (no redundant square rows):

    * chunked prefill — long prompts prefilled in bounded-memory
      query chunks against the growing cache;
    * prefix-LM suffix rows (ops/prefix_lm.py) — suffix queries
      against the full sequence without recomputing prefix rows;
    * cross-attention — ``causal=False`` with any Tq/Tk.

    Each side pads independently to its own block multiples; padded
    keys are masked via the true key length, padded q rows are
    sliced off. Gradients flow to q, k and v (same fused backward,
    rectangular grid). For Tq == Tk with q_offset == 0, prefer the
    square :func:`flash_attention` (same kernels, tuned defaults).
    """
    if interpret is None:
        interpret = use_interpret()
    b, tq0, h, d = q.shape
    tk0 = k.shape[1]
    if q_offset is None:
        q_offset = tk0 - tq0
    if causal and q_offset < 0:
        raise ValueError(
            f"causal rectangular attention needs q_offset >= 0 "
            f"(got {q_offset}): q rows before key 0 would attend "
            "nothing"
        )
    if window is not None:
        # The band compares run in key coordinates with the same
        # q_offset shift as the causal compare — Mistral chunked
        # prefill: each chunk does O(chunk * window) work, dead kv
        # blocks below the band skipped.
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires "
                "causal=True"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window > q_offset + tq0:
            window = None  # band covers every visible key
    if scale is None:
        scale = 1.0 / (d**0.5)
    if scale != 1.0 and math.frexp(scale)[0] == 0.5:
        q = q * jnp.asarray(scale, q.dtype)
        scale = 1.0

    # Per-side blocks: q-side sizes bound by Tq, k-side by Tk. Same
    # rules as the square wrapper, applied per side: requests larger
    # than the side substitute the padded base (so tuned configs that
    # work on the square kernel keep working here), the coprime guard
    # runs on the in-range requests, and every final block is rounded
    # up to the 8-sublane tile (short suffixes like Tq=23 would
    # otherwise emit an unloweable 23-row block; the round-up costs
    # at most 7 pad rows).
    def side(req, req_bwd, t, which):
        cap = max(t, 8)
        dflt = default_block_sizes(t)[which]
        # Round to the 8-sublane tile BEFORE the coprime guard — the
        # guard must judge the blocks that actually pad, or rounding
        # could silently reintroduce the inflation it rejects (e.g.
        # 24/12 -> 24/16, lcm 24 -> 48).
        r1 = -(-(req or dflt) // 8) * 8
        r2 = -(-(req_bwd or req or dflt) // 8) * 8
        in_range = [r for r in (r1, r2) if r <= cap]
        unit = _check_block_chain(in_range, t) if in_range else 1
        padded_base = -(-max(8, math.ceil(t / unit) * unit) // 8) * 8
        return tuple(
            r if r <= cap else padded_base for r in (r1, r2)
        )

    bq, bqb = side(block_q, block_q_bwd, tq0, 0)
    bk, bkb = side(block_k, block_k_bwd, tk0, 1)
    pad_q = (-tq0) % math.lcm(bq, bqb)
    pad_k = (-tk0) % math.lcm(bk, bkb)

    def to_kernel(x, pad):
        x = jnp.transpose(x, (0, 2, 1, 3))
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x

    qk = to_kernel(q, pad_q)
    kk_, vk = to_kernel(k, pad_k), to_kernel(v, pad_k)
    static = (
        causal, window, scale, bq, bk, bqb, bkb, tk0, interpret,
        q_offset,
    )
    if return_lse:
        o, lse = _per_device_flash(_flash_lse, qk, kk_, vk, static)
        o = o[:, :, :tq0].transpose(0, 2, 1, 3)
        return o.astype(q.dtype), lse[:, :, 0, :tq0]
    o = _per_device_flash(_flash, qk, kk_, vk, static)
    return o[:, :, :tq0].transpose(0, 2, 1, 3).astype(q.dtype)


def blocks_kwargs(attn_blocks: Optional[tuple]) -> dict:
    """(bq, bk, bqb, bkb) config tuple -> flash call kwargs — the one
    definition of the ``attn_blocks`` contract (model configs carry
    the tuple; gpt.default_attention_for and ops/prefix_lm.py unpack
    it through here)."""
    if attn_blocks is None:
        return {}
    bq, bk, bqb, bkb = attn_blocks
    return dict(
        block_q=bq, block_k=bk, block_q_bwd=bqb, block_k_bwd=bkb
    )
