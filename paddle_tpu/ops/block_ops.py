"""Block decoding (``models/block_moe.py``): a step forwards, for every
slot, the ``L`` rows of the block it is generating, and the tokens
committed to that block so far live on the device, ``[S, L]`` int32 a
decode program (a bundle's ``state_vars``).

Positions are cut into blocks of ``L`` from 0.  A position is MASKED
until a token is committed to it: its row's input is the mask token.
Masked-ness is a matter of position alone (rows of the block behind the
newest committed token), never of a token's value.

``block_rows`` turns a step's feeds into the block's rows: the newest
committed token ``Token`` at position ``Pos`` and ``Lens``, the rows
through the END of the block being forwarded (0: a free slot).  With
``start = Lens - L`` and ``at = Pos - start``:

* ``0 <= at < L``: the token is committed at row ``at`` of the block (and
  kept in the state); rows behind it are masked.
* ``at < 0`` (``Pos`` lies in the block before): the block is opened, all
  of its rows masked; nothing is committed.

``Pick`` is the one-hot of the leftmost masked row, ``at + 1``, whose
logits predict that row's own token; a block with no masked row left (the
pass that stores its K/V) picks its last row, and yields nothing.

``block_tail`` reads, in a prefill, the tokens of the block that the row
``Last`` marks lies in: what seeds the state of a slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op


def block_rows(token, pos, lens, block, mask_id):
    """``token``, ``pos``, ``lens`` [S, 1] int32; ``block`` [S, L] int32.
    Returns ``ids`` [S, L], ``row_pos`` [S, L], ``row_lens`` [S * L, 1]
    (the slot's ``lens`` on each of its rows), ``pick`` [S, L] float32
    one-hot, and the new ``block``."""
    L = block.shape[1]
    j = jnp.arange(L, dtype=jnp.int32)[None, :]
    start = lens - L
    at = pos - start
    block = jnp.where((j == at) & (lens > 0), token, block)
    ids = jnp.where(j <= at, block, jnp.int32(mask_id))
    pick = (j == jnp.clip(at + 1, 0, L - 1)).astype(jnp.float32)
    row_lens = jnp.broadcast_to(lens, block.shape).reshape(-1, 1)
    return ids, jnp.maximum(start, 0) + j, row_lens, pick, block


def _infer_block_rows(op, block):
    state = block.var(op.input("Block")[0])
    if state.shape is None:
        raise ShapeInferenceSkip()
    S, L = (int(d) for d in state.shape)
    for slot, shape, dtype in (("Ids", (S, L), "int32"),
                               ("RowPos", (S, L), "int32"),
                               ("RowLens", (S * L, 1), "int32"),
                               ("Pick", (S, 1, L), "float32")):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = shape, dtype


@register_op("block_rows", infer_shape=_infer_block_rows, no_gradient=True,
             stateful_outputs=("BlockOut",))
def block_rows_lower(ctx):
    """Token, Pos, Lens [S, 1] int32; Block [S, L] int32 (persistable:
    the tokens committed to each slot's block).  attr mask_id.  Ids,
    RowPos [S, L] int32; RowLens [S * L, 1] int32; Pick [S, 1, L]
    float32; BlockOut names Block itself."""
    ids, row_pos, row_lens, pick, state = block_rows(
        ctx.input("Token"), ctx.input("Pos"), ctx.input("Lens"),
        ctx.input("Block"), int(ctx.attr("mask_id")))
    ctx.set_output("Ids", ids)
    ctx.set_output("RowPos", row_pos)
    ctx.set_output("RowLens", row_lens)
    ctx.set_output("Pick", pick[:, None, :])
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
