"""Greedy self-speculative decoding by a model's own multi-token
prediction (MTP) module (``models/decoder.mtp_module``,
``models/window_moe.py``): a decode turn forwards TWO rows a slot, the
committed token ``c`` at position ``p`` and a draft ``d`` at ``p + 1``,
and yields one or two tokens.

With ``L_0, L_1`` the main model's logits of the two rows and ``a =
argmax L_0``, ``b = argmax L_1``: the draft is ACCEPTED where ``a == d``
(the slot yields ``d`` and ``b`` and advances two rows; ``b`` is the
committed token at ``p + 2``), else the slot yields ``a`` alone and
advances one (``a`` is committed at ``p + 1``, over the draft's stale
K/V row).  Either way the yielded tokens are the main model's greedy
tokens: a draft decides how many come a turn, never which.

The MTP module's row ``i`` takes ``(h_i, E[t_{i+1}])`` and predicts
``t_{i+2}``: after the verify it runs on ``(h_p, E[a])`` and, where the
draft was accepted, on ``(h_{p+1}, E[b])``, fills its own cache rows
``p`` (and ``p + 1``) and its last live row's argmax is the NEXT turn's
draft, kept in a per-slot state array ``[S, 1]`` int32 (a bundle's
``state_vars``) that a prefill's last chunk seeds.

* ``spec_rows``: a turn's feeds -> the two rows.  ``On`` 0 turns the
  draft row off (a blocking step that commits one token; a slot whose
  draft row would lie past ``max_len``): the row is DEAD: its K/V land
  nowhere, it takes no routed expert, and the turn yields one token.
* ``spec_verify``: the two rows' logits -> the turn's yield ``[S, 3]``
  (first token, second token or -1, how many: 0 for a free slot), and
  the MTP module's rows.
* ``spec_draft``: the MTP module's logits of each slot's last live row
  -> the draft state.
* ``spec_next_ids`` / ``spec_seed_draft``: a prefill chunk's side of the
  same: the ids the MTP rows embed (the prompt shifted by one, the
  prompt's last row taking the main model's own first token) and the
  slot's first draft (every chunk leaves its last row's pick there; the
  prompt's last chunk runs last).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.registry import ShapeInferenceSkip, register_op

_I32 = jnp.int32


def _first_argmax(x):
    """The greedy pick of each row of ``x`` [R, V]: the first index on
    ties, as ``np.argmax``."""
    return jnp.argmax(x, axis=-1).astype(_I32)


def spec_rows(token, draft, pos, lens, on, max_len):
    """``token``, ``draft``, ``pos``, ``lens``, ``on`` [S, 1] int32
    (``lens``: rows INCLUDING the committed token, 0 = free slot).
    Returns ``(ids [S, 2], row_pos [S, 2], end [S, 1], row_lens [S * 2,
    1])``: ``end`` the rows through the draft's row, dead or not (the
    paged op places a step's rows at ``end - 2`` and ``end - 1``; 0 = a
    free slot), ``row_lens`` what each row sees (its own included; 0 =
    dead: it lands nowhere)."""
    live = lens > 0
    drafted = live & (on > 0) & (pos + 1 < max_len)
    ids = jnp.concatenate([token, jnp.where(drafted, draft, 0)], axis=1)
    row_pos = jnp.concatenate([pos, pos + 1], axis=1)
    row_lens = jnp.concatenate(
        [lens, jnp.where(drafted, lens + 1, 0)], axis=1)
    end = jnp.where(live, lens + 1, 0)
    return (ids.astype(_I32), row_pos.astype(_I32), end.astype(_I32),
            row_lens.reshape(-1, 1).astype(_I32))


def spec_verify(logits, ids, row_lens):
    """``logits`` [S * 2, V] float32 (a slot's two rows in order);
    ``ids`` [S, 2]; ``row_lens`` [S * 2, 1].  Returns ``(out [S, 3],
    next_ids [S, 2], end [S, 1], mtp_row_lens [S * 2, 1])``: ``out`` =
    (first token, second token or -1, count); the MTP module's rows
    embed ``next_ids`` = (a, b), its second row live where the draft was
    accepted."""
    S = ids.shape[0]
    picks = _first_argmax(logits).reshape(S, 2)
    lens = row_lens.reshape(S, 2)
    live = lens[:, :1] > 0
    accepted = (lens[:, 1:] > 0) & (picks[:, :1] == ids[:, 1:])
    count = jnp.where(live, 1 + accepted.astype(_I32), 0)
    out = jnp.concatenate(
        [picks[:, :1], jnp.where(accepted, picks[:, 1:], -1), count], axis=1)
    mtp_lens = jnp.concatenate(
        [lens[:, :1], jnp.where(accepted, lens[:, :1] + 1, 0)], axis=1)
    return (out.astype(_I32), picks,
            jnp.where(live, lens[:, :1] + 1, 0).astype(_I32),
            mtp_lens.reshape(-1, 1).astype(_I32))


def _infer_spec_rows(op, block):
    token = block.var(op.input("Token")[0])
    if token.shape is None:
        raise ShapeInferenceSkip()
    S = int(token.shape[0])
    for slot, shape in (("Ids", (S, 2)), ("RowPos", (S, 2)),
                        ("End", (S, 1)), ("RowLens", (S * 2, 1))):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = shape, "int32"


@register_op("spec_rows", infer_shape=_infer_spec_rows, no_gradient=True)
def spec_rows_lower(ctx):
    """Token, Draft, Pos, Lens, On [S, 1] int32.  attr max_len.  Ids,
    RowPos [S, 2]; End [S, 1]; RowLens [S * 2, 1], all int32."""
    ids, row_pos, end, row_lens = spec_rows(
        ctx.input("Token"), ctx.input("Draft"), ctx.input("Pos"),
        ctx.input("Lens"), ctx.input("On"), int(ctx.attr("max_len")))
    ctx.set_output("Ids", ids)
    ctx.set_output("RowPos", row_pos)
    ctx.set_output("End", end)
    ctx.set_output("RowLens", row_lens)


def _infer_spec_verify(op, block):
    ids = block.var(op.input("Ids")[0])
    if ids.shape is None:
        raise ShapeInferenceSkip()
    S = int(ids.shape[0])
    for slot, shape in (("Out", (S, 3)), ("NextIds", (S, 2)),
                        ("MtpEnd", (S, 1)), ("MtpRowLens", (S * 2, 1))):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = shape, "int32"
    logits = block.var(op.input("Logits")[0])
    first = block.var(op.output("First")[0])
    first.dtype = logits.dtype
    if logits.shape is not None:
        first.shape = (S, int(logits.shape[-1]))


@register_op("spec_verify", infer_shape=_infer_spec_verify, no_gradient=True)
def spec_verify_lower(ctx):
    """Logits [S * 2, V] float32; Ids [S, 2]; RowLens [S * 2, 1].  Out
    [S, 3] (first token, second or -1, count); NextIds [S, 2]; MtpEnd
    [S, 1] and MtpRowLens [S * 2, 1]: the MTP module's rows; First [S,
    V]: the logits of each slot's first row."""
    logits = ctx.input("Logits")
    out, next_ids, end, row_lens = spec_verify(
        logits, ctx.input("Ids"), ctx.input("RowLens"))
    ctx.set_output("First", logits.reshape(out.shape[0], 2, -1)[:, 0])
    ctx.set_output("Out", out)
    ctx.set_output("NextIds", next_ids)
    ctx.set_output("MtpEnd", end)
    ctx.set_output("MtpRowLens", row_lens)


def _infer_pick(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        raise ShapeInferenceSkip()
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = (int(x.shape[0]), int(x.shape[-1])), x.dtype


@register_op("spec_pick_row", infer_shape=_infer_pick, no_gradient=True)
def spec_pick_row_lower(ctx):
    """X [S, 2, d]; Verdict [S, 3] (``spec_verify``'s Out).  Out [S, d]:
    each slot's LAST live row (row 1 where the count is 2)."""
    x = ctx.input("X")
    second = ctx.input("Verdict")[:, 2:3] > 1
    ctx.set_output("Out", jnp.where(second, x[:, 1], x[:, 0]))


def _infer_nothing(op, block):
    """Every output names a persistable state array, declared already."""


@register_op("spec_draft", infer_shape=_infer_nothing, no_gradient=True,
             stateful_outputs=("DraftOut",))
def spec_draft_lower(ctx):
    """Logits [S, V] float32 (the MTP module's, of each slot's last live
    row); Lens [S, 1] (0 = free slot: its draft stays); Draft [S, 1]
    int32 persistable.  DraftOut names Draft itself."""
    draft = ctx.input("Draft")
    new = _first_argmax(ctx.input("Logits"))[:, None]
    ctx.set_output("DraftOut", jnp.where(ctx.input("Lens") > 0, new,
                                         draft).astype(draft.dtype))


def _infer_next_ids(op, block):
    ids = block.var(op.input("NextIds")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = ids.shape, "int32"


@register_op("spec_next_ids", infer_shape=_infer_next_ids, no_gradient=True)
def spec_next_ids_lower(ctx):
    """NextIds [1, C] int32 (the chunk's tokens shifted by one; -1 where
    the host does not know the next token: behind the prompt's last
    row); Logits [1, V] (the main model's, of the chunk's last row).  Out
    [1, C]: NextIds with the main model's own pick where it is -1."""
    first = _first_argmax(ctx.input("Logits"))[:, None]
    nxt = ctx.input("NextIds")
    ctx.set_output("Out", jnp.where(nxt < 0, first, nxt).astype(_I32))


@register_op("spec_seed_draft", infer_shape=_infer_nothing,
             no_gradient=True, stateful_outputs=("DraftOut",))
def spec_seed_draft_lower(ctx):
    """Logits [1, V] (the MTP module's, of the prompt's last row); Last
    [1, C]; Slot [1, 1] int32; Draft [S, 1] persistable.  Where the
    chunk holds the prompt's last row the slot's draft becomes the
    pick; DraftOut names Draft itself."""
    draft = ctx.input("Draft")
    slot = ctx.input("Slot").reshape(-1)[0].astype(_I32)
    old = jax.lax.dynamic_slice_in_dim(draft, slot, 1, 0)
    new = jnp.where(jnp.sum(ctx.input("Last")) > 0,
                    _first_argmax(ctx.input("Logits"))[:, None], old)
    ctx.set_output("DraftOut", jax.lax.dynamic_update_slice_in_dim(
        draft, new.astype(draft.dtype), slot, 0))
