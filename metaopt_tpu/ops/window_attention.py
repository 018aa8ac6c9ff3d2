"""The causal kernels under a window no wider than a tile: one slab, one pass.

``attention._causal_fwd_kernel`` / ``_causal_bwd_kernel`` walk the tiles of
the resident sequence that a program's tile can see and fold each into an
online softmax. Under a window of 512 at tiles of 512 that walk is two
tiles, both crossed by an edge of the mask: 1024 keys read, masked and
exponentiated for the 512 a query sees. Here a program takes its tile in
SUB-TILES of ``sub`` (:data:`SUB`) and reads for each ONE slab of the other
sequence, ``window + sub`` long, that begins where the sub-tile's window
begins:

- forward, queries ``q1 .. q1 + sub - 1`` see keys ``q1 - window + 1 .. q1
  + sub - 1``: the slab ``[q1 - window, q1 + sub)``. Every key a query sees
  is in it, so the softmax is one pass (one max, one exponential, one sum,
  then ``v @ p``; no running maximum, no rescaled accumulator, no loop).
- backward, keys ``k1 .. k1 + sub - 1`` are seen by queries ``k1 .. k1 +
  sub + window - 2``: the slab ``[k1, k1 + sub + window)`` of q, dO, lse
  and delta; a sub-tile's dK and dV are complete in it, and dQ is added
  into the head's float32 scratch.

Of a slab only the first and the last ``sub`` positions are crossed by an
edge: the nearest block by the diagonal (key ``c`` of it is seen by query
``a`` iff ``c <= a``), the farthest by the window's far edge (iff ``a <
c``), the same triangle and its complement; the blocks between are seen
whole. The order of the TEXT is the schedule: the compiler packs into one
instruction what stands together and otherwise keeps the order it is
given, and a sub-tile taken from scores to output before the next begins
leaves the MXU waiting on the VPU and the VPU on the MXU (no faster than
the walk, forward). So the forward goes block by block of ``sub`` keys
with three sub-tiles in flight: the scores of one (MXU) beside the
exponentials of another (VPU) and the values of a third (MXU); and the
backward, whose five products bind it, takes each step over all the
sub-tiles of a tile before the next. The tile at the head of the sequence
(forward) and at its tail (backward) has no positions beyond the end, and
its place is static: it is one tile against itself, masked by position.

Everything else is ``attention._flash_causal``'s: feature-major operands,
the transposed score tile, one program a (row, query head, tile), the
head's K/V head by the block index, q.k and v at widths of their own,
bfloat16 operands with float32 accumulation and statistics, ``out`` and
``lse`` named for remat, the calls named ``flash_fwd`` / ``flash_bwd``
under the scope ``attention.core``. :func:`slab_sub` is the one rule for
which calls come here (``attention.flash_attention`` asks it, and
:func:`window_kernels` for what ``trial.setup`` says of a layer).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from metaopt_tpu.ops.attention import (_NEG_BIG, _NT, _call, _derived_block,
                                       _dot, _feature_major, _heads_last,
                                       _k_tiles_of, _kept, _rel, _seen)
from metaopt_tpu.utils import trace

#: a sub-tile: one lane tile of the score slab
SUB = 128
#: how many sub-tiles the forward's three stages stand apart
_LAGS = (0, 2, 4)


def slab_sub(window: Optional[int], block: int, length: int) -> Optional[int]:
    """The sub-tile of the slab kernels for a call under ``window`` at
    tiles of ``block`` over a (padded) ``length``, or None: the walk. From
    what the call sees alone: a window no wider than a tile (a wider one
    walks tiles that are mostly unmasked: 9 for 8 seen at 4096), both whole
    lane tiles, so that every slab starts on one."""
    if window is None or window > block or window % SUB or block % SUB \
            or length % block:
        return None
    return SUB


def slab_len(window: int, sub: int) -> int:
    """Positions of the other sequence a sub-tile reads."""
    return window + sub


def window_kernels(window: int, length: int) -> dict:
    """What ``trial.setup`` says of a layer under ``window`` over rows of
    ``length``: the kernels its calls take (``slab`` or ``walk``), their
    tile and, of the slab kernels, the sub-tile; ``walked_over_seen``, the
    keys a program reads for each key one of its queries sees, from the
    functions that size the slab and choose the walk's tiles."""
    block, padded = _derived_block(length)
    sub = slab_sub(window, block, padded)
    if sub:
        return {"kernels": "slab", "tile": block, "sub": sub,
                "walked_over_seen": slab_len(window, sub) / window}
    lo, _, _, hi = _k_tiles_of(padded - block, block, block, padded // block,
                               window)
    return {"kernels": "walk", "tile": block,
            "walked_over_seen": int(hi - lo) * block / min(window, padded)}


def _at(start, size: int):
    """``size`` positions from ``start``, a whole number of lane tiles in."""
    return pl.ds(pl.multiple_of(start, SUB), size)


def _slab_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, window: int,
                     sub: int):
    """One (batch row, query head, q tile) program. Shapes in VMEM as
    ``attention._causal_fwd_kernel``'s: q (1, Dqk, Bq); k (1, Dqk, S) and
    v (1, Dv, S), the head's K/V head; o (1, Dv, Bq); lse (1, 1, 1, Bq)."""
    bq = q_ref.shape[2]
    n, blocks = bq // sub, slab_len(window, sub) // sub
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():  # the head of the sequence: no key before 0
        st = jnp.where(_seen(_rel(bq, bq), 0, window),
                       _dot(k_ref[0, :, :bq].T, q_ref[0]), _NEG_BIG)
        m = jnp.max(st, axis=0, keepdims=True)                 # (1, Bq)
        pt = jnp.exp(st - m)
        l = jnp.sum(pt, axis=0, keepdims=True)
        vt = v_ref[0, :, :bq]
        o_ref[0] = (_dot(vt, pt.astype(vt.dtype)) * (1.0 / l)).astype(
            o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l)

    if k_ref.shape[2] == bq:
        return

    @pl.when(j > 0)
    def _():
        reach = _at(j * bq - window, window + bq)
        kt = k_ref[0, :, reach].T                       # (window + Bq, Dqk)
        vt = v_ref[0, :, reach]                         # (Dv, window + Bq)
        near = _rel(sub, sub) >= 0                      # key c, query a: c <= a
        mine = lambda t: slice(t * sub, (t + 1) * sub)  # noqa: E731
        # block b of sub-tile t's slab, in the tile's reach
        keys = lambda t, b: slice((t + b) * sub, (t + b + 1) * sub)  # noqa: E731
        st, top, pt, mass, acc = {}, {}, {}, {}, {}

        def score(t, b):
            """Block b of sub-tile t's scores (keys, queries), masked where
            an edge crosses it, into the sub-tile's maximum."""
            s = _dot(kt[keys(t, b)], q_ref[0, :, mine(t)])
            if b == 0:
                s = jnp.where(near, _NEG_BIG, s)        # the window's edge
            if b == blocks - 1:
                s = jnp.where(near, s, _NEG_BIG)        # the diagonal
            st[t, b] = s
            top[t] = s if b == 0 else jnp.maximum(top[t], s)
            if b == blocks - 1:
                top[t] = jnp.max(top[t], axis=0, keepdims=True)  # (1, sub)

        def soften(t, b):
            """Its probabilities, not yet divided, into the sub-tile's sum."""
            p = jnp.exp(st.pop((t, b)) - top[t])
            mass[t] = p if b == 0 else mass[t] + p
            pt[t, b] = p.astype(vt.dtype)
            if b == blocks - 1:
                mass[t] = jnp.sum(mass[t], axis=0, keepdims=True)

        def weigh(t, b):
            """Its values, into the sub-tile's output and lse."""
            part = _dot(vt[:, keys(t, b)], pt.pop((t, b)))      # (Dv, sub)
            acc[t] = part if b == 0 else acc[t] + part
            if b == blocks - 1:
                o_ref[0, :, mine(t)] = (acc.pop(t) * (1.0 / mass[t])).astype(
                    o_ref.dtype)
                lse_ref[0, 0, :, mine(t)] = top[t] + jnp.log(mass[t])

        # A software pipeline in the TEXT: the compiler packs what stands
        # together, and keeps the order it is given. Block by block, the
        # scores of sub-tile t (MXU) stand beside the exponentials of t - 2
        # (VPU) and the values of t - 4 (MXU); in one run per stage the
        # three follow one another (1.76 ms a call at the seventh cell's
        # shape for 1.48, PERF.md section 6, PR 46).
        for step in range(n + _LAGS[-1]):
            for b in range(blocks):
                for stage, lag in zip((score, soften, weigh), _LAGS):
                    if 0 <= step - lag < n:
                        stage(step - lag, b)


def _slab_bwd_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, dq_ref,
                     dk_ref, dv_ref, acc_ref, *, window: int, sub: int):
    """One (batch row, query head, k tile) program, as
    ``attention._causal_bwd_kernel``: this head's share of the tile's dK
    and dV, float32 (the heads of a group are summed outside), and the
    tile's share of the head's dQ, summed over the head's K tiles in the
    float32 scratch. Shapes in VMEM: k (1, Dqk, Bk), v (1, Dv, Bk); dk, dv
    likewise; q, dq (1, Dqk, S), dO (1, Dv, S); lse, delta (1, 1, 1, S)."""
    bk, s = k_ref.shape[2], q_ref.shape[2]
    n, slab = bk // sub, slab_len(window, sub)
    j, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kt = k_ref[0]                                           # (Dqk, Bk)
    kb = kt.T
    vb = v_ref[0].T

    @pl.when(j == last)
    def _():  # the tail of the sequence: no query past the end
        qs = slice(s - bk, s)
        qt, gt = q_ref[0, :, qs], g_ref[0, :, qs]
        st = jnp.where(_seen(_rel(bk, bk), 0, window), _dot(kb, qt),
                       _NEG_BIG)
        pt = jnp.exp(st - lse_ref[0, 0, :, qs])
        dst = (pt * (_dot(vb, gt) - delta_ref[0, 0, :, qs])).astype(qt.dtype)
        acc_ref[:, qs] += _dot(kt, dst)
        dk_ref[0] = _dot(qt, dst, _NT)
        dv_ref[0] = _dot(gt, pt.astype(gt.dtype), _NT)
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)

    if s == bk:
        return

    @pl.when(j < last)
    def _():
        k0 = j * bk
        reach = _at(k0, bk + window)
        qr, gr = q_ref[0, :, reach], g_ref[0, :, reach]  # (D, Bk + window)
        mine = lambda t: slice(t * sub, (t + 1) * sub)  # noqa: E731
        seen_by = lambda t: slice(t * sub, t * sub + slab)  # noqa: E731
        row = lambda ref, t: ref[0, 0, :, _at(k0 + t * sub, slab)]  # noqa: E731
        near = _rel(sub, sub) >= 0
        # each step over all the sub-tiles before the next
        sts = [_dot(kb[mine(t)], qr[:, seen_by(t)]) for t in range(n)]
        dps = [_dot(vb[mine(t)], gr[:, seen_by(t)]) for t in range(n)]
        pts = [jnp.exp(jnp.concatenate([
            jnp.where(near, st[:, :sub], _NEG_BIG),     # the diagonal
            st[:, sub:slab - sub],
            jnp.where(near, _NEG_BIG, st[:, slab - sub:])],  # the far edge
            axis=1) - row(lse_ref, t)) for t, st in enumerate(sts)]
        dsts = [(pt * (dp - row(delta_ref, t))).astype(qr.dtype)
                for t, (pt, dp) in enumerate(zip(pts, dps))]
        dv_ref[0] = jnp.concatenate(
            [_dot(gr[:, seen_by(t)], pts[t].astype(gr.dtype), _NT)
             for t in range(n)], axis=1)                # dV.T (Dv, Bk)
        dk_ref[0] = jnp.concatenate(
            [_dot(qr[:, seen_by(t)], dsts[t], _NT) for t in range(n)],
            axis=1)                                     # dK.T (Dqk, Bk)
        for t in range(n):                              # dQ.T, slab by slab
            acc_ref[:, _at(k0 + t * sub, slab)] += _dot(kt[:, mine(t)],
                                                        dsts[t])


_jit = functools.partial(
    jax.jit, static_argnames=("window", "block", "interpret"))


@_jit
def _forward(q, k, v, window, block, interpret):
    """(out, lse): ``attention._causal_forward``'s call on the slab
    kernel. q (B, S, H, Dqk); k (B, S, Hkv, Dqk), v (B, S, Hkv, Dv)."""
    b, s, h, dqk = q.shape
    dv = v.shape[3]
    group = h // k.shape[2]
    at_q = lambda i, hh, j: (i, hh, j)            # noqa: E731
    at_kv = lambda i, hh, j: (i, hh // group, 0)  # noqa: E731
    out, lse = _call(
        _slab_fwd_kernel, "flash_fwd", (b, h, s // block),
        ("parallel", "parallel", "parallel"),
        [pl.BlockSpec((1, dqk, block), at_q),
         pl.BlockSpec((1, dqk, s), at_kv), pl.BlockSpec((1, dv, s), at_kv)],
        [pl.BlockSpec((1, dv, block), at_q),
         pl.BlockSpec((1, 1, 1, block), lambda i, hh, j: (i, hh, 0, j))],
        [jax.ShapeDtypeStruct((b, h * dv, s), q.dtype),
         jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        [], [_feature_major(q), _feature_major(k), _feature_major(v)],
        interpret, window=window, sub=slab_sub(window, block, s))
    return _heads_last(out, h), lse


@_jit
def _backward(q, k, v, out, lse, g, window, block, interpret):
    """(dq, dk, dv): ``attention._causal_backward``'s call on the slab
    kernel."""
    b, s, h, dqk = q.shape
    hkv, dv_rows = k.shape[2], v.shape[3]
    group = h // hkv
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[:, :, None]  # (B, H, 1, S)
    whole = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, s), lambda i, hh, j: (i, hh, 0))
    kv_spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, block), lambda i, hh, j: (i, hh // group, j))
    stat = pl.BlockSpec((1, 1, 1, s), lambda i, hh, j: (i, hh, 0, 0))
    tile = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, block), lambda i, hh, j: (i, hh, j))
    dq, dk, dv = _call(
        _slab_bwd_kernel, "flash_bwd", (b, h, s // block),
        ("parallel", "parallel", "arbitrary"),
        [whole(dqk), whole(dv_rows), kv_spec(dqk), kv_spec(dv_rows), stat,
         stat], [whole(dqk), tile(dqk), tile(dv_rows)],
        [jax.ShapeDtypeStruct((b, h * dqk, s), q.dtype),
         jax.ShapeDtypeStruct((b, h * dqk, s), jnp.float32),
         jax.ShapeDtypeStruct((b, h * dv_rows, s), jnp.float32)],
        [(dqk, s)],
        [_feature_major(q), _feature_major(g), _feature_major(k),
         _feature_major(v), lse, delta],
        interpret, window=window, sub=slab_sub(window, block, s))

    def group_sum(x, like):
        """A K/V head's gradient: the sum over the query heads reading it."""
        d = like.shape[3]
        x = x.reshape(b, hkv, group, d, s).sum(axis=2).astype(like.dtype)
        return _heads_last(x.reshape(b, hkv * d, s), hkv)

    return _heads_last(dq, h), group_sum(dk, k), group_sum(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_window(q, k, v, window, block, interpret):
    return _flash_window_fwd(q, k, v, window, block, interpret)[0]


@trace.scope("attention.core")
def _flash_window_fwd(q, k, v, window, block, interpret):
    out, lse = _kept(*_forward(q, k, v, window, block, interpret))
    return out, (q, k, v, out, lse)


@trace.scope("attention.core")
def _flash_window_bwd(window, block, interpret, residuals, g):
    return _backward(*residuals, g, window, block, interpret)


_flash_window.defvjp(_flash_window_fwd, _flash_window_bwd)


def flash_window(q, k, v, window, block_q, block_k, interpret):
    """``attention._flash_causal`` for a call :func:`slab_sub` takes
    (``block_q == block_k``, the tile)."""
    return _flash_window(q, k, v, window, block_q, interpret)
