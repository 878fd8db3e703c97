"""Generative causal LM with a prefill/decode phase split — the model
side of the continuous-batching serving runtime (``paddle_tpu/gen/``).

One set of parameters (shared names) is exported as TWO inference
programs, the vLLM/Orca-style entry pair:

* **prefill** — batch of ONE prompt, dynamic (bucketed) length: runs the
  full causal forward over the prompt, fetches the next-token logits at
  the last real position plus the per-layer K/V projections (masked to
  zero on pad rows) that seed the request's KV-cache slot.  The length
  axis is dynamic; callers pad to a ``lod.row_bucket`` edge so the jit
  key is the bucket, not the exact prompt length.
* **decode** (:func:`build_paged_decode_program`) — ONE token for every
  slot of a fixed pool: the persistable cache lives as
  ``[num_pages, page_len, H*D]`` fixed-size pages plus a per-slot page
  table; a step scatters the new token's K/V row into its slot's tail
  page (an in-place persistable update, so the cache never leaves the
  device) and attends only the pages covering ``[0, len)`` per slot —
  decode reads scale with live prefix length, not ``max_len``.  The
  page-table feed's width is bucketed (``page_buckets``), so the jit
  key is the bucket — admission and eviction never recompile.

The third entry, :func:`gen_lm_train_program`, is the teacher-forced
training graph over the same parameter names (and the model-zoo lint
gate's view of this model).
"""

from __future__ import annotations

import numpy as np

import paddle_tpu.layers as layers
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.models.decoder import (META_FILENAME, PAGE_LEN_DEFAULT,
                                       data, decode_inputs,
                                       default_page_buckets, export_bundle,
                                       group, op, persistable, program_role)
from paddle_tpu.param_attr import ParamAttr

__all__ = ["GenConfig", "build_prefill_program",
           "build_paged_decode_program", "gen_lm_train_program",
           "export_gen_model", "META_FILENAME", "PAGE_LEN_DEFAULT",
           "paged_cache_var_names", "default_page_buckets"]


class GenConfig:
    """Toy-scale causal LM hyperparameters (decode mechanics, not model
    quality, are what the gen runtime exercises)."""
    vocab_size = 64
    d_model = 32
    n_head = 2
    d_head = 16          # n_head * d_head == d_model
    n_layer = 2
    d_ffn = 64
    max_len = 64         # cache length L (bucketed max sequence length)
    eos_id = -1          # <0: no EOS in the base model (requests may
                         # override per call)


def _pa(name, **kw):
    return ParamAttr(name=name, **kw)


def _pos_table(hp):
    from paddle_tpu.models.transformer import position_encoding_init
    return position_encoding_init(hp.max_len, hp.d_model)


def _embed(ids, pos_ids, hp):
    """Shared token + position embedding (works for [B, T] prefill ids
    and [S, 1] decode ids — lookup_table squeezes a trailing 1); group
    ``embed``."""
    with group("embed"):
        word = layers.embedding(ids, size=[hp.vocab_size, hp.d_model],
                                param_attr=_pa("genlm_word_emb"))
        word = layers.scale(word, scale=float(hp.d_model) ** 0.5)
        pos = layers.embedding(
            pos_ids, size=[hp.max_len, hp.d_model],
            param_attr=_pa("genlm_pos_emb", trainable=False,
                           initializer=NumpyArrayInitializer(
                               _pos_table(hp))))
        return word + pos


def _ln(x, idx, tag):
    return layers.layer_norm(
        x, begin_norm_axis=len(x.shape) - 1,
        param_attr=_pa(f"genlm{idx}_{tag}.scale"),
        bias_attr=_pa(f"genlm{idx}_{tag}.bias"))


def _ffn(x, hp, idx):
    h = layers.fc(x, hp.d_ffn, num_flatten_dims=2, act="relu",
                  param_attr=_pa(f"genlm{idx}_ffn1.w"),
                  bias_attr=_pa(f"genlm{idx}_ffn1.b"))
    return layers.fc(h, hp.d_model, num_flatten_dims=2,
                     param_attr=_pa(f"genlm{idx}_ffn2.w"),
                     bias_attr=_pa(f"genlm{idx}_ffn2.b"))


def _qkv(x, hp, idx):
    """Q/K/V projections over [B, T, d] (or [S, 1, d])."""
    def proj(role):
        return layers.fc(x, hp.n_head * hp.d_head, num_flatten_dims=2,
                         bias_attr=False,
                         param_attr=_pa(f"genlm{idx}_{role}.w"))
    return proj("q"), proj("k"), proj("v")


def _heads(x, hp, length):
    """[B, T, H*D] -> [B, H, T, D]; ``length`` may be -1 (dynamic)."""
    x = layers.reshape(x, shape=[x.shape[0], length, hp.n_head, hp.d_head])
    return layers.transpose(x, perm=[0, 2, 1, 3])


def _merge_heads(ctx, hp, length):
    """[B, H, T, D] -> [B, T, H*D]."""
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    return layers.reshape(
        ctx, shape=[ctx.shape[0], length, hp.n_head * hp.d_head])


def _attend(q, k, v, bias, hp, idx, q_len, k_len):
    """Scaled-dot-product attention with an additive ``bias`` mask
    (broadcastable against [B, H, Sq, Sk] scores)."""
    scale = float(hp.d_head) ** -0.5
    qh = _heads(q, hp, q_len)
    kh = _heads(k, hp, k_len)
    vh = _heads(v, hp, k_len)
    scores = layers.matmul(qh, kh, transpose_y=True, alpha=scale)
    weights = layers.softmax(scores, bias=bias)
    ctx = layers.matmul(weights, vh)
    ctx = _merge_heads(ctx, hp, q_len)
    return layers.fc(ctx, hp.d_model, num_flatten_dims=2, bias_attr=False,
                     param_attr=_pa(f"genlm{idx}_attnout.w"))


def _block_tail(x, attn, hp, idx):
    """Post-norm: the residual add and norm behind the attention are
    ``attn``'s, the FFN with its add and norm ``dense``."""
    with group("attn"):
        x = _ln(x + attn, idx, "ln1")
    with group("dense"):
        return _ln(x + _ffn(x, hp, idx), idx, "ln2")


def paged_cache_var_names(hp):
    """The decode program's persistable page-pool tensor names, in the
    (k, v) per-layer order the prefill fetch list follows."""
    names = []
    for i in range(hp.n_layer):
        names.append(f"genlm_paged_k_{i}")
        names.append(f"genlm_paged_v_{i}")
    return names


# ---------------------------------------------------------------------------
# prefill: one prompt, dynamic (bucketed) length
# ---------------------------------------------------------------------------

@program_role("gen_prefill")
def build_prefill_program(hp):
    """Build the prefill forward in the CURRENT program guard.

    Feeds (all length-dynamic; callers pad to a bucket):
      ``gen_ids`` [1, T] int32, ``gen_pos`` [1, T] int32,
      ``gen_mask`` [1, T] f32 (1 = real token),
      ``gen_attn_bias`` [1, 1, T, T] f32 (combined causal+padding
      additive bias), ``gen_last`` [1, T] f32 (one-hot of the last real
      position).
    Fetches: ``[logits [1, V], k_0, v_0, k_1, v_1, ...]`` with each
    K/V [1, T, H*D] zeroed on pad rows (cache hygiene: decode add-writes
    land on zeros).
    """
    ids = data("gen_ids", [1, -1], "int32")
    pos = data("gen_pos", [1, -1], "int32")
    mask = data("gen_mask", [1, -1])
    bias = data("gen_attn_bias", [1, 1, -1, -1])
    last = data("gen_last", [1, -1])

    x = _embed(ids, pos, hp)
    kv = []
    for i in range(hp.n_layer):
        with group("attn"):
            q, k, v = _qkv(x, hp, i)
            k_m = layers.elementwise_mul(k, mask, axis=0)
            v_m = layers.elementwise_mul(v, mask, axis=0)
            kv += [k_m, v_m]
            attn = _attend(q, k_m, v_m, bias, hp, i, q_len=-1, k_len=-1)
        x = _block_tail(x, attn, hp, i)
    with group("head"):
        last3 = layers.reshape(last, shape=[1, 1, -1])
        lasth = layers.matmul(last3, x)                    # [1, 1, d]
        lasth = layers.reshape(lasth, shape=[-1, hp.d_model])
        logits = layers.fc(lasth, hp.vocab_size, bias_attr=False,
                           param_attr=_pa("genlm_logits.w"))
    feeds = ["gen_ids", "gen_pos", "gen_mask", "gen_attn_bias", "gen_last"]
    return feeds, [logits] + kv


# ---------------------------------------------------------------------------
# decode: one token for every slot; page-pool cache, page-table feed
# bucketed by page count
# ---------------------------------------------------------------------------

@program_role("gen_decode")
def build_paged_decode_program(hp, num_slots, page_len, num_pages):
    """Build the single-token decode step in the CURRENT program guard.

    Feeds (static except the bucketed page-table width):
      ``gen_token`` [S, 1] int32, ``gen_pos`` [S, 1] int32,
      ``gen_page_table`` [S, P] int32 — per-slot page ids in prefix
      order; ``P`` is DYNAMIC, padded by the predictor to a
      ``page_buckets`` edge so the jit key is the bucket,
      ``gen_lens`` [S, 1] int32 — rows INCLUDING the current token
      (0 = free slot: nothing written, logits garbage, never read).
    Persistable state: per-layer ``genlm_paged_k_i`` / ``genlm_paged_v_i``
    [num_pages, page_len, H*D], updated in place by the
    ``paged_attention`` op (scatter of the step's K/V row into the
    slot's tail page, then attention over ONLY the table's pages).
    Fetches: ``logits`` [S, V].
    """
    S, PL, NP = int(num_slots), int(page_len), int(num_pages)
    hd = hp.n_head * hp.d_head
    token, pos, page_table, lens = decode_inputs(S)
    caches = {name: persistable(name, [NP, PL, hd], "float32")
              for name in paged_cache_var_names(hp)}

    x = _embed(token, pos, hp)                         # [S, d]
    with group("embed"):
        x = layers.reshape(x, shape=[S, 1, hp.d_model])
    for i in range(hp.n_layer):
        with group("attn"):
            q, k, v = _qkv(x, hp, i)                   # [S, 1, H*D]
            pk = caches[f"genlm_paged_k_{i}"]
            pv = caches[f"genlm_paged_v_{i}"]
            ctxv = op("paged_attention",
                      {"Q": q, "K": k, "V": v, "KCache": pk, "VCache": pv,
                       "PageTable": page_table, "Lens": lens},
                      {"Out": "float32", "KCacheOut": pk, "VCacheOut": pv},
                      {"n_head": int(hp.n_head),
                       "scale": float(hp.d_head) ** -0.5})["Out"]
            attn = layers.fc(ctxv, hp.d_model, num_flatten_dims=2,
                             bias_attr=False,
                             param_attr=_pa(f"genlm{i}_attnout.w"))
        x = _block_tail(x, attn, hp, i)
    with group("head"):
        x2 = layers.reshape(x, shape=[S, hp.d_model])
        logits = layers.fc(x2, hp.vocab_size, bias_attr=False,
                           param_attr=_pa("genlm_logits.w"))
    feeds = ["gen_token", "gen_pos", "gen_page_table", "gen_lens"]
    return feeds, [logits]


# ---------------------------------------------------------------------------
# training graph (teacher-forced) — also the model-zoo lint gate's view
# ---------------------------------------------------------------------------

def gen_lm_train_program(batch_size, seq_len, hp: GenConfig = None):
    """Causal-LM training forward in the current program guard; returns
    ``(avg_cost, feed_names)``.  Feeds: ``gen_ids`` / ``gen_labels``
    [B, T] int32."""
    hp = hp or GenConfig()
    B, T = int(batch_size), int(seq_len)

    ids = layers.data(name="gen_ids", shape=[B, T], dtype="int32",
                      append_batch_size=False)
    labels = layers.data(name="gen_labels", shape=[B, T], dtype="int32",
                         append_batch_size=False)
    pos_np = np.tile(np.arange(T, dtype="int32"), (B, 1))
    pos = layers.assign(pos_np)
    tri = np.triu(np.full((T, T), -1e9, dtype="float32"), 1)
    bias = layers.assign(tri.reshape(1, 1, T, T))

    x = _embed(ids, pos, hp)
    for i in range(hp.n_layer):
        with group("attn"):
            q, k, v = _qkv(x, hp, i)
            attn = _attend(q, k, v, bias, hp, i, q_len=T, k_len=T)
        x = _block_tail(x, attn, hp, i)
    with group("head"):
        logits = layers.fc(x, hp.vocab_size, num_flatten_dims=2,
                           bias_attr=False,
                           param_attr=_pa("genlm_logits.w"))
        logits2d = layers.reshape(logits, shape=[B * T, hp.vocab_size])
        labels2d = layers.reshape(labels, shape=[B * T, 1])
        cost = layers.softmax_with_cross_entropy(logits2d, labels2d)
        avg_cost = layers.mean(x=cost)
    return avg_cost, ["gen_ids", "gen_labels"]


# ---------------------------------------------------------------------------
# export: one parameter set -> prefill/ + decode/ + gen_meta.json
# ---------------------------------------------------------------------------

def export_gen_model(dirname, hp: GenConfig = None, num_slots=8,
                     prompt_buckets=None, paged=True,
                     page_len=PAGE_LEN_DEFAULT, num_pages=None,
                     page_buckets=None):
    """Export a generation bundle (``decoder.export_bundle`` has the
    layout and the pool's defaults): ``<dirname>/prefill/``,
    ``<dirname>/decode/`` and ``<dirname>/gen_meta.json``.  Returns
    ``dirname``.

    ``paged`` is accepted for callers written when a dense ``[num_slots,
    max_len]`` layout could be exported too; that layout was removed and
    anything but ``True`` raises."""
    if paged is not True:
        raise ValueError(
            f"export_gen_model(paged={paged!r}): the dense KV layout was "
            f"removed; every bundle is the page-pool one (drop the keyword)")
    hp = hp or GenConfig()
    return export_bundle(
        dirname, hp, "gen_lm.export_gen_model",
        lambda *pool: build_prefill_program(hp),
        lambda *pool: build_paged_decode_program(hp, *pool),
        paged_cache_var_names(hp), hp.n_layer, num_slots=num_slots,
        prompt_buckets=prompt_buckets, page_len=page_len,
        num_pages=num_pages, page_buckets=page_buckets)
