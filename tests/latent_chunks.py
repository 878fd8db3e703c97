"""One drill of a full latent layer's CHUNK op, shared by
``test_latent_moe.py`` (heads of 192 | 128 lanes, no selection),
``test_glm_dsa.py`` (256 | 256 under a selection) and
``test_dots3_note.py`` (192 | 128 under a selection): a prompt whose
last chunk runs through ``mla_ops.mla_attention_chunk`` over the slot's
pages equals the whole-sequence ``mla_ops.mla_attention`` on the chunk's
real rows, in the kernel's form (interpret mode: a (head, key block)
expanded where it is used) and in the composed one."""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import dsa_ops, mla_ops, window_ops

#: rows a chunk (the least the causal block rule admits at one query head
#: a K/V head), rows of the page bucket, rows a page
C, T, PAGE = 512, 2048, 64
#: (start, real rows of the chunk): a prompt's first chunk, one that
#: stands off every block edge, one in the middle of the bucket with pad
#: rows behind its real ones, and the one that ends the bucket
STARTS = [(0, C), (64, C), (768, 300), (T - C, C)]
START_IDS = ["first", "at_64", "mid_bucket_pad_rows", "bucket_end"]


def chunk_is_the_whole_sequence(nope, rope, v_dim, start, n, top_k=0,
                                kernel=True, n_head=2, latent=128, seed=0):
    """Rows ``0 .. start - 1`` lie in the slot's pages (every other row
    of the pool holds 7: stale), the chunk brings ``n`` real rows and
    pad rows behind them; ``top_k`` > 0: under the top-``top_k`` of
    seeded index scores, the chunk's selection made over the bucket as
    ``dsa_ops.causal_select`` makes it."""
    total, width = start + n, latent + 128
    key = jax.random.split(jax.random.PRNGKey(seed + start), 4)
    q = jax.random.normal(key[0], (T, n_head * (nope + rope))) * 0.3
    rows = jnp.pad(jax.random.normal(key[1], (T, latent + rope)),
                   ((0, 0), (0, width - latent - rope)))
    w_kvb = jax.random.normal(key[2], (latent, n_head * (nope + v_dim))) \
        * 0.1
    sizes = (n_head, nope, rope, v_dim, 0.125)
    whole_select = select = None
    if top_k:
        scores = jax.random.normal(key[3], (T, T))
        whole_select = dsa_ops.causal_select(
            scores[:total, :total], jnp.ones(total), top_k)
        select = dsa_ops.causal_select(
            scores[start:start + C], (jnp.arange(C) < n).astype(jnp.float32),
            top_k, start=start)
    want = mla_ops.mla_attention(q[:total], rows[:total], w_kvb,
                                 jnp.ones(total), *sizes, flash=False,
                                 select=whole_select)[start:]
    pages = T // PAGE
    table = jnp.asarray(np.random.RandomState(seed).permutation(
        pages + 5)[None, :pages], jnp.int32)
    held = jnp.where((jnp.arange(T) < start)[:, None], rows, 7.0)
    pool = jnp.full((pages + 5, PAGE, width), 7.0).at[table[0]].set(
        held.reshape(pages, PAGE, width))
    chunk = lambda x: jnp.where(
        (jnp.arange(C) < n)[:, None], x[start:start + C], 0.0)
    blocks = window_ops.flash_blocks
    try:
        if not kernel:
            window_ops.flash_blocks = lambda *a, **k: None
        got, pool = mla_ops.mla_attention_chunk(
            chunk(q), chunk(rows), w_kvb, pool, table, jnp.int32(start),
            (jnp.arange(C) < n)[None], *sizes, select=select,
            interpret=True)
    finally:
        window_ops.flash_blocks = blocks
    assert got.shape == (C, n_head * v_dim)
    assert np.allclose(got[:n], want, atol=2e-4)
    # the real rows lie at their positions, a pad row nowhere
    after = np.asarray(pool[table[0]]).reshape(T, width)
    assert np.array_equal(after[:total], np.asarray(rows[:total]))
    assert (after[total:] == 7.0).all()
