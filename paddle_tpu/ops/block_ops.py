"""Block decoding (``models/block_moe.py``): a step forwards, for every
slot, ``2L`` rows: half A, the ``L`` rows of the block the slot is
generating, and half B, the block behind it.  The tokens committed to
block A so far live on the device, ``[S, L]`` int32 a decode program (a
bundle's ``state_vars``).

Positions are cut into blocks of ``L`` from 0.  A position is MASKED
until a token is committed to it: its row's input is the mask token.
Masked-ness is a matter of position alone (rows of the block behind the
newest committed token), never of a token's value.

``block_rows`` turns a step's feeds into the rows: the newest committed
token ``Token`` at position ``Pos`` and ``Lens``, the rows through the
END of the block ``Pos`` lies in (0: a free slot).  With ``start = Lens
- L`` and ``at = Pos - start`` (``0 <= at < L``) the token is committed
at row ``at`` of block A (and kept in the state); A's rows behind it are
masked.  Row ``j`` of the ``2L`` stands at position ``start + j``.

* ``at < L - 1``: half B is DEAD (row limit 0): its K/V land nowhere, it
  takes no routed expert and nothing reads its output.  A's rows see the
  rows under ``Lens``.
* ``at == L - 1``, the token completes block A (the host knows it as
  ``(Pos + 1) % L == 0``): A is forwarded with every token committed and
  its rows see the rows under ``Lens``: these are the block's final K/V,
  what later blocks read.  B is the next block, every row masked; its
  rows see the rows under ``Lens + L``, A's among them: the block-causal
  mask.

``Pick`` is the one-hot of the leftmost masked row, ``at + 1`` of the
``2L`` (B's first row where A is complete), whose logits predict that
row's own token.  The state keeps block A's tokens after it is complete:
the next step commits at row 0 and the rows behind are masked by
position.

``block_tail`` reads, in a prefill, the tokens of the block that the row
``Last`` marks lies in: what seeds the state of a slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op


def block_rows(token, pos, lens, block, mask_id):
    """``token``, ``pos``, ``lens`` [S, 1] int32; ``block`` [S, L] int32.
    Returns ``ids`` [S, 2L], ``row_pos`` [S, 2L], ``row_lens`` [S * 2L,
    1] (what each row sees; 0: a dead row), ``pick`` [S, 2L] float32
    one-hot, ``end`` [S, 1] (rows through the last of the ``2L``: where
    ``paged_attention`` puts them; 0 for a free slot) and the new
    ``block``."""
    L = block.shape[1]
    j = jnp.arange(2 * L, dtype=jnp.int32)[None, :]
    live = lens > 0
    start = lens - L
    at = pos - start
    block = jnp.where((j[:, :L] == at) & live, token, block)
    ids = jnp.where(j <= at, jnp.tile(block, (1, 2)), jnp.int32(mask_id))
    pick = (j == jnp.clip(at + 1, 0, 2 * L - 1)).astype(jnp.float32)
    # half A under ``lens``; half B under ``lens + L`` where A is complete
    behind = jnp.where(live & (at == L - 1), lens + L, 0)
    row_lens = jnp.where(j < L, lens, behind)
    return (ids, jnp.maximum(start, 0) + j, row_lens.reshape(-1, 1), pick,
            jnp.where(live, lens + L, 0), block)


def _infer_block_rows(op, block):
    state = block.var(op.input("Block")[0])
    if state.shape is None:
        raise ShapeInferenceSkip()
    S, L = (int(d) for d in state.shape)
    for slot, shape, dtype in (("Ids", (S, 2 * L), "int32"),
                               ("RowPos", (S, 2 * L), "int32"),
                               ("RowLens", (S * 2 * L, 1), "int32"),
                               ("Pick", (S, 1, 2 * L), "float32"),
                               ("End", (S, 1), "int32")):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = shape, dtype


@register_op("block_rows", infer_shape=_infer_block_rows, no_gradient=True,
             stateful_outputs=("BlockOut",))
def block_rows_lower(ctx):
    """Token, Pos, Lens [S, 1] int32; Block [S, L] int32 (persistable:
    the tokens committed to each slot's block).  attr mask_id.  Ids,
    RowPos [S, 2L] int32; RowLens [S * 2L, 1] int32; Pick [S, 1, 2L]
    float32; End [S, 1] int32; BlockOut names Block itself."""
    ids, row_pos, row_lens, pick, end, state = block_rows(
        ctx.input("Token"), ctx.input("Pos"), ctx.input("Lens"),
        ctx.input("Block"), int(ctx.attr("mask_id")))
    ctx.set_output("Ids", ids)
    ctx.set_output("RowPos", row_pos)
    ctx.set_output("RowLens", row_lens)
    ctx.set_output("Pick", pick[:, None, :])
    ctx.set_output("End", end)
    ctx.set_output("BlockOut", state)


def block_tail(ids, last, length):
    """``ids`` [T] int32, ``last`` [T] (one-hot of a row): the ``length``
    ids of the block that row lies in, [length] int32."""
    at = jnp.argmax(last).astype(jnp.int32)
    return jax.lax.dynamic_slice_in_dim(ids, at // length * length, length)


def _infer_block_tail(op, block):
    v = block.var(op.output("Out")[0])
    v.shape, v.dtype = (1, int(op.attr("block_length"))), "int32"


@register_op("block_tail", infer_shape=_infer_block_tail, no_gradient=True)
def block_tail_lower(ctx):
    """Ids [1, T] int32; Last [1, T] one-hot.  attr block_length.  Out
    [1, block_length] int32."""
    ctx.set_output("Out", block_tail(
        ctx.input("Ids")[0], ctx.input("Last")[0],
        int(ctx.attr("block_length")))[None])
