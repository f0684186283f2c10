"""Family ``kimi_linear`` broken on purpose, one path a control
(cells under ``benchmark/controls/kimi_cells``):

* ``no_carry``: the rule starts every chunk of 64 tokens from a zero
  state (each chunk is run as a sequence of its own), so nothing
  crosses a chunk boundary;
* ``no_delta``: the ``-beta k k^T S'`` term is dropped, which leaves
  gated linear attention: ``S_t = diag(a_t) S_{t-1} + beta_t k_t v_t^T``;
* ``no_shared``: the shared expert is left out of every expert layer;
* ``rope_on_mla``: the latent layer's shared key part ``k_r`` and the
  query's last 64 columns are rotated (``rope_theta``), where the
  configuration states ``mla_use_nope``.

The program has no switch for any of these: each puts a broken
function in the program's place while the loss is traced, zeroes the
leaves before the loss reads them, or hands the loss an attention
callable that rotates first.

No control breaks the router. ``correct`` compares a sequence's mean
loss, this chip's share routes 1/32 of the (token, choice) pairs, and
a wrong weight or choice among random experts moves that mean at
second order: a softmax in the sigmoid's place and the choice bias
added to the weights both read under the tolerance on the chip
(PERF.md section 6, PR 53), and a control that passes is no control.
``tests/test_kimi_linear.py`` holds the router, the bias and the held
path to the reference on the CPU.
"""

from __future__ import annotations

import contextlib

NAMES = ("no_carry", "no_delta", "no_shared", "rope_on_mla")


@contextlib.contextmanager
def _in_place_of(module, name, value):
    honest = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, honest)


def _rule_without_carry(kda):
    def rule(q, k, v, g, beta, chunk=64, sub_block=16):
        bsz, t = q.shape[:2]
        cut = lambda x: x.reshape((bsz * (t // chunk), chunk) + x.shape[2:])
        o = kda(*map(cut, (q, k, v, g, beta)), chunk, sub_block)
        return o.reshape((bsz, t) + o.shape[2:])

    return rule


def _rule_without_delta(kda_module):
    """Gated linear attention in the chunked rule's own pieces: the
    pair matrix q k^T with its decays, and the state scan with
    nothing read back from the state (``w`` zero, the inverse one)."""
    import jax.numpy as jnp

    def rule(q, k, v, g, beta, chunk=64, sub_block=16):
        dtype = q.dtype
        b, t, h, _ = q.shape
        n = t // chunk

        def chunks(x):
            x = x.astype(jnp.float32).reshape(b, n, chunk, h, -1)
            return jnp.transpose(x, (1, 0, 3, 2, 4))

        q, k, v, g = map(chunks, (q, k, v, g))
        u = chunks(beta[..., None]) * v
        cum = jnp.cumsum(g, axis=-2)
        _, qk = kda_module._pair_decays(q, k, cum, sub_block, dtype)
        last = cum[..., -1:, :]
        states = kda_module._chunk_states(
            jnp.zeros_like(k), k * jnp.exp(last - cum),
            jnp.exp(last[..., 0, :]), u, dtype,
        )
        o = kda_module._state_product(
            "...cd,...dv->...cv", q * jnp.exp(cum), states, dtype
        ) + kda_module._product("...ts,...sv->...tv", qk, u, dtype)
        o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, h, -1)
        return o.astype(dtype)

    return rule


def _without_shared(params):
    import jax
    import jax.numpy as jnp

    def layer(tree):
        if "moe" not in tree:
            return tree
        moe = dict(tree["moe"])
        moe["shared"] = jax.tree.map(jnp.zeros_like, moe["shared"])
        return dict(tree, moe=moe)

    return dict(
        params, layers={k: layer(v) for k, v in params["layers"].items()}
    )


def _rotating(attn_fn, width: int, theta: float):
    """``attn_fn`` on queries and keys whose last ``width`` columns
    are rotated by position (huggingface's split-halves convention)."""
    import jax.numpy as jnp

    def rotate(x):
        t, half = x.shape[1], width // 2
        inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
        keep, x1, x2 = (
            x[..., :-width], x[..., -width:-half], x[..., -half:]
        )
        return jnp.concatenate(
            [keep, x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )

    def attention(q, k, v, **kw):
        return attn_fn(rotate(q), rotate(k), v, **kw)

    return attention


def broken(name: str, loss):
    """``loss`` (params, tokens, targets) with the path ``name`` says
    broken; the other arguments pass through."""
    from dlrover_tpu.models import kimi_linear as model
    from dlrover_tpu.ops import kda as kda_module

    if name == "no_shared":
        return lambda params, *batch: loss(_without_shared(params), *batch)
    if name == "rope_on_mla":
        cfg = loss.keywords["cfg"]
        attn_fn = _rotating(
            model.default_attention_for(cfg), cfg.qk_rope, 10000.0
        )
        return lambda *args: loss(*args, attn_fn=attn_fn)
    if name == "no_carry":
        swap = (kda_module, "kda", _rule_without_carry(kda_module.kda))
    elif name == "no_delta":
        swap = (kda_module, "kda", _rule_without_delta(kda_module))
    else:
        raise ValueError(f"no control {name!r}: one of {NAMES}")

    def traced_broken(*args):
        with _in_place_of(*swap):
            return loss(*args)

    return traced_broken
