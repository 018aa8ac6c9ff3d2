"""The embedding's row lookup, with a gradient rule of its own.

``embed_rows(table, ids)`` is the lookup ``nn.Embed(dtype=bfloat16)``
makes: the rows ``ids`` name of the float32 ``table``, as bfloat16. Left to
autodiff, its transpose is a scatter-add of a step's rows into a table of
zeros, which XLA walks a token at a time: on a v5e 15.1 ms for 8192 rows of
2560 into 37 984, and as long when the ids are sorted (PERF.md section 6,
PR 41). The rule here writes each table row once:

- the integer plan (no gradient flows through it): the ids sorted, stable,
  an id outside the table moved behind every other (it names no row: what
  the scatter's ``mode="drop"`` did);
- the incoming rows taken in that order, as float32;
- one Pallas kernel, ``embed_rows_bwd``, over blocks of ``BLOCK`` sorted
  tokens: it sums the rows of equal ids **in float32** in VMEM and writes
  the table in aligned groups of eight rows, a copy a group, onto a table
  of zeros it is aliased to. Sorted ids visit a group once, so a group is
  written whole (the rows nobody named as zeros) and nothing reads a table
  row back from HBM. 1.1-1.8 ms at the five decoder cells' shapes where
  the scatter-add took 2.2-16.2.

:func:`embed_gradient_route` names the route from what a call can see (the
one rule; no option overrides it): ``"sorted"`` on one TPU device,
``"take"`` elsewhere (the CPU, a mesh of several devices: the kernel has
no ``shard_map``), the plain lookup whose transpose is XLA's scatter-add.
Forward and backward sit under the scope ``embed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from metaopt_tpu.utils import trace

#: sorted tokens a program of the kernel (a block of float32 rows in VMEM,
#: twice: 2 x 3.9 MB at a width of 3840)
BLOCK = 256
#: tokens a trip of the kernel's loop (Mosaic unrolls all or nothing)
_UNROLL = 8
#: rows a copy: a float32 tile's sublanes (a slice of the table in HBM has
#: to be whole tiles); row r lies in group r >> _SHIFT
_SHIFT = 3
_GROUP = 1 << _SHIFT
#: lanes a tile: the kernel's rows are whole tiles wide
_LANES = 128
#: groups that wait in VMEM for their copy to the table
_SLOTS = 8


def embed_gradient_route(mesh=None) -> str:
    """The route the lookup's gradient takes under ``mesh``: ``"sorted"``
    (this module's rule) on one TPU device, ``"take"`` (the plain lookup,
    autodiff's scatter-add) elsewhere."""
    on_one_tpu = jax.default_backend() == "tpu" and (
        mesh is None or mesh.size == 1)
    return "sorted" if on_one_tpu else "take"


@trace.scope("embed")
def _take(table, ids):
    """``nn.Embed(dtype=bfloat16)``'s lookup."""
    return jnp.take(table.astype(jnp.bfloat16), ids, axis=0)


def embed_rows(table, ids, *, interpret=None):
    """The rows ``ids`` (any shape, integer) name of ``table`` (rows,
    width), float32, as bfloat16: ``ids.shape + (width,)``. Ids are
    promised inside the table; one outside reads what ``jnp.take`` reads
    and its gradient is dropped. ``interpret`` (tests): take the sorted
    rule whatever the backend, its kernel interpreted or not."""
    from metaopt_tpu.parallel.mesh import active_mesh

    if interpret is None and embed_gradient_route(active_mesh()) == "take":
        return _take(table, ids)
    return _sorted(table, ids, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sorted(table, ids, interpret):
    return _take(table, ids)


def _sorted_fwd(table, ids, interpret):
    # the table rides along for its shape alone: the rule reads no value
    return _take(table, ids), (table, ids)


def _sorted_bwd(interpret, residuals, g):
    table, ids = residuals
    with trace.scope("embed"):
        rows, width = table.shape
        ids = ids.reshape(-1)
        tokens = ids.shape[0]
        beyond = -(-rows // _GROUP) * _GROUP  # the first group past the table
        block = min(BLOCK, -(-tokens // _UNROLL) * _UNROLL)
        pad = -tokens % block
        ids = jnp.where((ids >= 0) & (ids < rows), ids, beyond)
        order = jnp.argsort(ids, stable=True)
        # the padding joins the ids outside the table: the kernel copies no
        # group at or past ``beyond``
        sorted_ids = jnp.pad(ids[order], (0, pad), constant_values=beyond)
        sorted_g = jnp.take(g.reshape(tokens, width), jnp.pad(order, (0, pad)),
                            axis=0).astype(jnp.float32)
        # whole lanes: the kernel slices rows out of (8, 128) tiles
        sorted_g = jnp.pad(sorted_g, ((0, 0), (0, -width % _LANES)))
        summed = _write_rows(sorted_ids, sorted_g, beyond, block, interpret)
        return summed[:rows, :width].astype(table.dtype), None


_sorted.defvjp(_sorted_fwd, _sorted_bwd)


def _rows_kernel(ids_ref, g_ref, zeros_ref, out_ref, groups, copied, state, *,
                 block, beyond):
    """A block of sorted tokens. ``ids_ref`` (1, block + 2) in SMEM: the
    block's token ``i``'s id at ``i + 1``, the ids of the tokens before and
    after the block at the ends. ``g_ref`` (block, width). ``out_ref``: the
    table in HBM, aliased onto ``zeros_ref``. ``groups`` (_SLOTS, _GROUP,
    width): the group being summed and those whose copy is under way;
    ``copied``: a semaphore a slot; ``state``: [the slot being summed, the
    copies started]."""
    del zeros_ref

    @pl.when(pl.program_id(0) == 0)
    def _():
        state[0] = 0
        state[1] = 0

    def wait(slot):
        pltpu.make_async_copy(groups.at[slot], out_ref.at[pl.ds(0, _GROUP)],
                              copied.at[slot]).wait()

    def token(i):
        here = ids_ref[0, i + 1]
        group = here >> _SHIFT
        slot = state[0]

        @pl.when(group != ids_ref[0, i] >> _SHIFT)
        def _():
            groups[slot] = jnp.zeros(groups.shape[1:], groups.dtype)

        row = (slot, pl.ds(here & (_GROUP - 1), 1), slice(None))
        groups[row] = groups[row] + g_ref[pl.ds(i, 1), :]

        @pl.when((group != ids_ref[0, i + 2] >> _SHIFT)
                 & (here < beyond))
        def _():
            pltpu.make_async_copy(
                groups.at[slot],
                out_ref.at[pl.ds(pl.multiple_of(group * _GROUP, _GROUP),
                                 _GROUP)],
                copied.at[slot]).start()
            after = (slot + 1) % _SLOTS

            @pl.when(state[1] + 1 >= _SLOTS)  # the ring has come round
            def _():
                wait(after)

            state[0] = after
            state[1] = state[1] + 1

    def several(k, carry):
        for u in range(_UNROLL):
            token(k * _UNROLL + u)
        return carry

    jax.lax.fori_loop(0, block // _UNROLL, several, 0)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        # the slot after the last copy's was waited for when that copy
        # started: the _SLOTS - 1 before it may still be under way
        def drain(k, carry):
            @pl.when(k < jnp.minimum(state[1], _SLOTS - 1))
            def _():
                wait((state[0] + _SLOTS - 1 - k) % _SLOTS)

            return carry

        jax.lax.fori_loop(0, _SLOTS - 1, drain, 0)


def _write_rows(sorted_ids, sorted_g, beyond, block, interpret):
    """(beyond, width) float32: row ``r`` the sum of ``sorted_g``'s rows
    whose id is ``r``, zeros where no id names it. ``sorted_ids`` (tokens,)
    ascending, ``tokens`` a whole number of ``block``; an id of ``beyond``
    or more names no row."""
    tokens, width = sorted_g.shape
    nowhere = jnp.full((1,), -_GROUP, jnp.int32)  # in no token's group
    ids = jnp.concatenate([nowhere, sorted_ids.astype(jnp.int32), nowhere])
    # a block's ids with its two neighbours': (blocks, 1, block + 2)
    ids = ids[jnp.arange(0, tokens, block)[:, None, None]
              + jnp.arange(block + 2)[None, None, :]]
    return pl.pallas_call(
        functools.partial(_rows_kernel, block=block, beyond=beyond),
        out_shape=jax.ShapeDtypeStruct((beyond, width), jnp.float32),
        grid=(tokens // block,),
        in_specs=[pl.BlockSpec((None, 1, block + 2), lambda j: (j, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((block, width), lambda j: (j, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((_SLOTS, _GROUP, width), jnp.float32),
                        pltpu.SemaphoreType.DMA((_SLOTS,)),
                        pltpu.SMEM((2,), jnp.int32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="embed_rows_bwd",
    )(ids, sorted_g, jnp.zeros((beyond, width), jnp.float32))
