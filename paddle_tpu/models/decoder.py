"""What the serving decoders share: the library ``gen_lm.py``,
``hybrid_moe.py``, ``latent_moe.py``, ``block_moe.py`` and
``window_moe.py`` build their programs from, and the ONE exporter of the
generation bundle (``<dirname>/prefill/``, ``<dirname>/decode/``,
``<dirname>/gen_meta.json``).

A builder writes what is its own: its configuration, its attention (and
what that caches), its pattern of layers, and its section of the bundle's
meta.  From here it takes

* the program vocabulary: :func:`op`, :func:`param`, :func:`matrix`,
  :func:`vector`, :func:`data`, :func:`persistable`;
* the blocks every pre-norm decoder has: :func:`rms`, :func:`head_norm`,
  :func:`embed`, :func:`live_rows`,
  :func:`logits`, :func:`gated_ffn`, :func:`routed_experts`, the
  two-sublayer :func:`decoder_layer` (over ONE residual, or over the
  ``hc_mult`` streams of hyper-connections: :func:`hc_copy_in`,
  :func:`hc_sublayer`, :func:`hc_sum_out`); the multi-token-prediction
  module any of them can append (:func:`mtp_module`, :func:`mtp_logits`)
  and the self-drafting turn built on it (:func:`draft_turn`,
  :func:`chunk_draft`);
* the head and the tail of its three programs: :func:`prefill_inputs` /
  :func:`last_row`, :func:`decode_inputs` / :func:`decode_fetches`,
  :func:`train_inputs` / :func:`train_loss`;
* the names its ops carry into the device trace: :func:`program_role`
  (which program of the bundle: :data:`ROLES`) and :func:`group` (which
  sublayer: :data:`GROUPS`);
* :class:`DecoderConfig` (``from_dict`` over a published ``config.json``)
  and :func:`export_bundle`.

Nothing here is selected by a model's name: what differs between two
decoders is passed in by the caller.  The order in which a function
makes its parameters and appends its ops is part of a program (it names
the temporaries and orders the startup program), so it is part of each
function's contract; ``tests/test_gen_bundle_programs.py`` holds every
builder's programs to their recorded digests.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os

import numpy as np

import paddle_tpu.layers as layers
from paddle_tpu import initializer as init_mod
from paddle_tpu.framework import (default_main_program, name_scope,
                                  open_name_scopes)
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.param_attr import ParamAttr

__all__ = ["META_FILENAME", "PAGE_LEN_DEFAULT", "DECODE_STATS",
           "CHUNK_ROWS", "ROLES", "GROUPS", "program_role", "group",
           "mtp_scope", "mhc_scope", "hc_copy_in", "hc_sublayer",
           "hc_sum_out", "draft_turn", "chunk_draft", "speculative_meta",
           "DecoderConfig", "default_page_buckets",
           "chunk_rows", "op", "param", "matrix",
           "vector", "data", "persistable", "rms", "head_norm", "embed",
           "live_rows", "logits",
           "gated_ffn", "routed_experts", "decoder_layer", "shared",
           "mtp_module", "mtp_logits", "prefill_inputs",
           "last_row", "decode_inputs", "decode_fetches", "train_inputs",
           "train_loss", "write_model", "export_bundle"]

META_FILENAME = "gen_meta.json"

#: default KV page length (rows per page)
PAGE_LEN_DEFAULT = 16

#: the columns of a decode step's second fetch, one row per expert layer
DECODE_STATS = [{"name": "moe_assignments", "reduce": "sum"},
                {"name": "moe_experts_touched", "reduce": "sum"},
                {"name": "moe_max_load", "reduce": "max"}]

# rows of a prefill chunk (the larger rung).  A chunk reads every matrix
# once, so it should hold several times the rows at which a v5e's
# products take as long as their operands' reads (~240), and it is what
# a live stream waits through between two of its tokens, so no more:
# PERF.md section 6 (PR 42, PR 46) has the chip's readings at 512 / 1024
# / 2048
CHUNK_ROWS = 1024

#: the outermost name scope of a serving program: which program of the
#: bundle an instruction of the device trace belongs to (the predictor
#: names its own two executables ``gen_turn`` and ``gen_seed``)
ROLES = ("gen_prefill", "gen_chunk", "gen_decode")

#: the name scope under the role: which sublayer (docs/observability.md
#: has what lies under each and the metric that reads it)
GROUPS = ("embed", "attn", "mixer", "experts", "dense", "head")


def program_role(role):
    """Decorator of a builder's ``build_prefill_program`` /
    ``build_chunk_program`` / ``build_paged_decode_program``: every op
    the function appends lies under the name scope ``role`` (one of
    :data:`ROLES`), whoever calls it.  A train program takes none."""
    if role not in ROLES:
        raise ValueError(f"program_role({role!r}): one of {ROLES}")

    def named(build):
        @functools.wraps(build)
        def build_under_role(*args, **kwargs):
            with name_scope(role):
                return build(*args, **kwargs)
        return build_under_role
    return named


def group(name):
    """The name scope of ONE sublayer (one of :data:`GROUPS`).  One group
    is open at a time: opened inside another it takes that one's place
    (``name_scope``'s ``instead_of``), so a path reads ``<role>/<group>``
    or ``<role>/mtp/<group>`` and never holds two."""
    if name not in GROUPS:
        raise ValueError(f"group({name!r}): one of {GROUPS}")
    return name_scope(name, instead_of=GROUPS)


@contextlib.contextmanager
def mtp_scope():
    """The MTP module's own name scope ``mtp``, outside its ops' groups
    (``<role>/mtp/<group>``); inside itself it opens nothing, so what a
    builder appends around :func:`mtp_logits` can share its scope."""
    if "mtp" in open_name_scopes():
        yield
    else:
        with name_scope("mtp"):
            yield


def mhc_scope():
    """The name scope ``mhc`` of a hyper-connection wrapper's own ops,
    INSIDE the group of the sublayer it wraps (``<role>/<group>/mhc``,
    ``<role>/mtp/<group>/mhc``): what the wrapper costs has a name of its
    own on the device trace and still counts with its sublayer."""
    return name_scope("mhc")


class DecoderConfig:
    """The plumbing of a decoder's configuration class: toy-scale
    defaults as class attributes, ``from_dict`` over the published keys
    of a ``config.json`` (``_KEYS`` maps a published key to the
    attribute that holds it; a key the class has no attribute for is
    dropped)."""
    _KEYS = {}

    @classmethod
    def from_dict(cls, cfg):
        hp = cls()
        for key, value in cfg.items():
            name = cls._KEYS.get(key, key)
            if hasattr(cls, name) and not name.startswith("_"):
                setattr(hp, name, value)
        return hp

    @property
    def held(self):
        """Experts a layer HOLDS of its ``n_routed_experts``, from
        ``expert_offset`` on: one chip's share of an expert-parallel
        deployment (``experts_held`` None: all of them)."""
        return int(self.n_routed_experts if self.experts_held is None
                   else self.experts_held)


def default_page_buckets(pages_per_slot):
    """Power-of-two page-count bucket ladder capped at ``pages_per_slot``
    (NOT :func:`lod.bucket_edges`, whose fallback ladder floors at 8 —
    page counts are small integers).  ``GenPredictor.plan_page_buckets``
    replaces this with a measured-workload ladder."""
    edges, b = [], 1
    while b < int(pages_per_slot):
        edges.append(b)
        b *= 2
    edges.append(int(pages_per_slot))
    return sorted(set(edges))


def chunk_rows(page_len, prompt_buckets, max_len, top=None):
    """The chunk rungs of a bundle whose prefill is a chunk program,
    ascending, from its shapes (``prompt_buckets`` bound and describe
    its prompts): the larger is ``top`` (None: ``CHUNK_ROWS``; no more
    than the longest prompt takes, whole pages); the smaller, half of it
    where that is whole pages too, is for a prompt's LAST chunk, which
    takes it where it fits, so that a prompt runs no more than half the
    larger rung in pad rows.  Every rung is an executable a page bucket to compile and
    to warm, so the half rung is left out where it buys little: in a
    bundle whose SHORTEST prompt bucket already spans two of the larger
    rung, where the pad rows it saves are a few hundredths of a
    prompt's."""
    page_len = int(page_len)
    top = min(int(top or CHUNK_ROWS), min(max(prompt_buckets),
                                          int(max_len)))
    top = max(-(-top // page_len) * page_len, page_len)
    half = top // 2
    if half % page_len or not half or min(prompt_buckets) >= 2 * top:
        return [top]
    return [half, top]


# ---------------------------------------------------------------------------
# the program vocabulary
# ---------------------------------------------------------------------------

def op(op_type, inputs, outputs, attrs=None):
    """Append ``op_type``; ``outputs`` maps slot -> dtype of a fresh
    temporary, or -> an existing variable (in-place state).  An input
    that is None is left out.  Returns ``{slot: variable}``."""
    helper = LayerHelper(op_type)
    outs = {slot: (helper.create_tmp_variable(v) if isinstance(v, str)
                   else v) for slot, v in outputs.items()}
    helper.append_op(type=op_type,
                     inputs={k: [v] for k, v in inputs.items()
                             if v is not None},
                     outputs={k: [v] for k, v in outs.items()},
                     attrs=attrs or {})
    return outs


def param(name, shape, dtype, init):
    """A named parameter of the CURRENT program (and its initialiser in
    the startup program)."""
    return layers.create_parameter(
        list(shape), dtype, attr=ParamAttr(name=name, initializer=init))


def matrix(hp, name, shape):
    """A matrix in ``hp.dtype``, Xavier-uniform over its last two axes."""
    fan = shape[-2] + shape[-1]
    limit = (6.0 / fan) ** 0.5
    return param(name, shape, hp.dtype, init_mod.Uniform(-limit, limit))


def vector(name, n, value):
    """A float32 vector of ``n`` times ``value`` (norm scales, biases,
    sink logits: what stays float32 in a bfloat16 model)."""
    return param(name, [n], "float32", init_mod.Constant(value))


def data(name, shape, dtype="float32"):
    """A feed of exactly ``shape`` (-1: dynamic, bucketed by the caller)."""
    return layers.data(name=name, shape=shape, dtype=dtype,
                       append_batch_size=False)


def persistable(name, shape, dtype):
    """Declare a cache of the CURRENT program: a persistable variable the
    step reads and writes in place (a page pool, a ring, a per-slot
    state).  It takes no gradient and no initialiser: the exporter
    writes it as zeros of ``dtype`` (:func:`export_bundle`)."""
    v = default_main_program().global_block().create_var(
        name=name, shape=list(shape), dtype=dtype)
    v.persistable = True
    v.stop_gradient = True
    return v


# ---------------------------------------------------------------------------
# the blocks of a pre-norm decoder
# ---------------------------------------------------------------------------

def rms(x, name, hp):
    """RMSNorm over the last axis with the float32 scale ``name``."""
    scale = vector(name, int(x.shape[-1]), 1.0)
    return op("rms_norm", {"X": x, "Scale": scale}, {"Out": hp.dtype},
              {"epsilon": float(hp.eps)})["Out"]


def head_norm(x, name, hp, n_head, width):
    """RMSNorm over each head's ``width`` lanes of ``x`` [..., n_head *
    width] (QK-norm), one float32 scale ``name`` a layer."""
    lead = [int(d) for d in x.shape[:-1]]
    rows = rms(layers.reshape(x, shape=[-1, width]), name, hp)
    return layers.reshape(rows, shape=lead + [n_head * width])


def embed(ids, hp, prefix, lead=None):
    """The token embedding ``{prefix}_emb`` (no position is added: the
    attention, or a mixer, carries it), reshaped to ``lead + [d]`` where
    ``lead`` is given (a decode step's ``[slots, rows a slot]``); group
    ``embed``."""
    limit = (6.0 / (hp.vocab_size + hp.hidden_size)) ** 0.5
    with group("embed"):
        x = layers.embedding(
            ids, size=[int(hp.vocab_size), int(hp.hidden_size)],
            dtype=hp.dtype,
            param_attr=ParamAttr(name=f"{prefix}_emb",
                                 initializer=init_mod.Uniform(-limit,
                                                              limit)))
        if lead is not None:
            x = layers.reshape(x, shape=list(lead) + [int(hp.hidden_size)])
        return x


def live_rows(mask):
    """``lens`` [rows, 1] int32 of a prefill's or a chunk's rows from its
    ``mask`` [1, rows]: 1 on a real row, 0 on a pad row, which takes no
    routed expert; group ``embed`` (it lays the step's rows out)."""
    with group("embed"):
        return layers.reshape(layers.cast(mask, "int32"), shape=[-1, 1])


def logits(x2, hp, prefix):
    """Final norm and the untied head over rows ``x2`` [R, d]; float32;
    group ``head``."""
    with group("head"):
        h = rms(x2, f"{prefix}_norm.scale", hp)
        head = matrix(hp, f"{prefix}_head.w", [int(hp.hidden_size),
                                               int(hp.vocab_size)])
        return op("matmul", {"X": h, "Y": head}, {"Out": "float32"},
                  {"out_dtype": "float32"})["Out"]


def gated_ffn(h, hp, prefix, width):
    """``W_d (silu(W_g h) * W_u h)`` of ``width``: a dense layer's FFN, a
    shared expert; group ``dense`` (also where a routed layer builds its
    shared expert inside its ``experts``)."""
    d = int(hp.hidden_size)
    with group("dense"):
        g = layers.matmul(h, matrix(hp, f"{prefix}_gate.w", [d, width]))
        u = layers.matmul(h, matrix(hp, f"{prefix}_up.w", [d, width]))
        a = op("swiglu", {"X": g, "Y": u}, {"Out": hp.dtype})["Out"]
        return layers.matmul(a, matrix(hp, f"{prefix}_down.w", [width, d]))


def routed_experts(h, hp, prefix, lens, *, experts, held, expert_offset,
                   scaling, scoring=None, bias=True, chunk_rows=None,
                   latent=None):
    """A routed-expert layer: ``moe_route`` over ALL ``experts`` on the
    full hidden state (the ``hp.num_experts_per_tok`` largest, weights
    renormalised where ``hp.norm_topk_prob``, times ``scaling``), then
    the experts of width ``hp.moe_intermediate_size`` over the ``held``
    the layer holds from ``expert_offset`` on (what the absent ones
    would add is left out).  Returns ``(out, stats)``, ``stats`` the
    layer's row of :data:`DECODE_STATS`.

    ``lens`` [rows, 1] int32 or None: a row with 0 (a free slot's, a pad
    row) has no assignment.  ``scoring``: the router's score where it is
    not the op's own sigmoid.  ``bias``: the router holds a correction
    bias ``{prefix}_gate.bias`` (it moves the choice only).
    ``chunk_rows``: the routed product's row chunk where the caller
    knows better than the op's default.  ``latent`` None: gated experts
    ``W_d (silu(W_g h) * W_u h)`` on the hidden state
    (``moe_experts_gated``; ``{prefix}_wg`` / ``_wu`` / ``_wd``).
    ``latent`` = L: the LatentMoE form, ungated experts (``moe_experts``;
    ``{prefix}_w1`` / ``_w2``) in a latent of L between a down- and an
    up-projection (``{prefix}_down.w`` / ``_up.w``).  A shared expert is
    its caller's addition.  Group ``experts``: the router, the routed
    product and the latent form's two projections."""
    d, F = int(hp.hidden_size), int(hp.moe_intermediate_size)
    with group("experts"):
        route_in = {"X": h,
                    "W": matrix(hp, f"{prefix}_gate.w", [d, experts])}
        if bias:
            route_in["Bias"] = vector(f"{prefix}_gate.bias", experts, 0.0)
        route_attrs = {"top_k": int(hp.num_experts_per_tok),
                       "scaling": float(scaling),
                       "norm_topk": bool(hp.norm_topk_prob)}
        if scoring is not None:
            route_attrs["scoring"] = scoring
        route = op("moe_route", route_in,
                   {"TopkIdx": "int32", "TopkWeight": "float32"},
                   route_attrs)
        attrs = {"expert_offset": int(expert_offset)}
        if chunk_rows is not None:
            attrs["chunk_rows"] = int(chunk_rows)
        if latent is None:
            kind, x = "moe_experts_gated", h
            weights = {"Wg": matrix(hp, f"{prefix}_wg", [held, d, F]),
                       "Wu": matrix(hp, f"{prefix}_wu", [held, d, F]),
                       "Wd": matrix(hp, f"{prefix}_wd", [held, F, d])}
        else:
            kind = "moe_experts"
            x = layers.matmul(h, matrix(hp, f"{prefix}_down.w",
                                        [d, latent]))
            weights = {"W1": matrix(hp, f"{prefix}_w1", [held, latent, F]),
                       "W2": matrix(hp, f"{prefix}_w2", [held, F, latent])}
        routed = op(kind, {"X": x, "TopkIdx": route["TopkIdx"],
                           "TopkWeight": route["TopkWeight"], **weights,
                           "Lens": lens},
                    {"Out": hp.dtype, "Stats": "int32"}, attrs)
        out = routed["Out"]
        if latent is not None:
            out = layers.matmul(out, matrix(hp, f"{prefix}_up.w",
                                            [latent, d]))
        return out, routed["Stats"]


def decoder_layer(x, hp, prefix, attention, ffn, *, routed, hc_mult=1):
    """One pre-norm layer of two sublayers, ``x <- x +
    attention(RMSNorm(x))`` then ``x <- x + ffn(RMSNorm(x))``, the norms'
    scales ``{prefix}_norm1.scale`` / ``_norm2.scale``.  ``attention(h)
    -> (out, kept)``: ``kept`` is whatever the caller wants of it (the
    rows that seed a cache, a selection); ``ffn(h) -> (out, stats or
    None)``.  Returns ``(x, kept, stats)``.

    A sublayer's norm and residual add go into the sublayer's group: the
    first is ``attn``; the second ``experts`` where the layer is
    ``routed``, else ``dense``.  A routed layer's second norm feeds its
    shared expert as well as its router and lies under ``experts``; the
    shared expert itself (:func:`gated_ffn`) is ``dense``.

    ``hc_mult`` = n > 1: ``x`` is the n residual STREAMS ``[..., n, d]``
    (:func:`hc_copy_in`) and each sublayer, its pre-norm included, is
    wrapped by :func:`hc_sublayer` (parameters ``{prefix}_hc1.*`` /
    ``_hc2.*``) where the one residual has its add."""
    if int(hc_mult) > 1:
        with group("attn"):
            x, kept = hc_sublayer(x, hp, f"{prefix}_hc1", lambda u: attention(
                rms(u, f"{prefix}_norm1.scale", hp)))
        with group("experts" if routed else "dense"):
            x, stats = hc_sublayer(x, hp, f"{prefix}_hc2", lambda u: ffn(
                rms(u, f"{prefix}_norm2.scale", hp)))
        return x, kept, stats
    with group("attn"):
        out, kept = attention(rms(x, f"{prefix}_norm1.scale", hp))
        x = x + out
    with group("experts" if routed else "dense"):
        out, stats = ffn(rms(x, f"{prefix}_norm2.scale", hp))
        return x + out, kept, stats


# ---------------------------------------------------------------------------
# hyper-connections: n residual streams (ops/mhc_ops.py)
# ---------------------------------------------------------------------------

def hc_copy_in(x, n):
    """The model's input ``x`` [..., d] copied into ``n`` residual streams
    ``[..., n, d]`` (arXiv:2409.19606 section 3); ``embed/mhc``."""
    rank = len(x.shape)
    with group("embed"), mhc_scope():
        return layers.expand(layers.unsqueeze(x, [rank - 1]),
                             [1] * (rank - 1) + [int(n), 1])


def hc_sum_out(x):
    """The streams ``[..., n, d]`` summed into the ONE residual ``[...,
    d]`` that a final norm (or an MTP module) takes; ``head/mhc``."""
    with group("head"), mhc_scope():
        return layers.reduce_sum(x, dim=len(x.shape) - 2)


def hc_sublayer(x, hp, name, sublayer):
    """ONE sublayer under manifold-constrained hyper-connections
    (``ops/mhc_ops.py``): ``u = H_pre x``, ``(y, kept) = sublayer(u)``,
    ``x <- H_res x + H_post^T y`` over the streams ``x`` [..., n, d];
    the three mappings are made from the streams themselves (``mhc_pre``;
    ``H_res`` balanced by ``hp.hc_sinkhorn_iters`` Sinkhorn rounds).
    Parameters, float32: ``{name}.phi`` [n d, n (n + 2)], ``{name}.alpha``
    [3], ``{name}.bias`` [n (n + 2)].  The wrapper's own ops lie under
    :func:`mhc_scope` inside the caller's group; the sublayer's are the
    caller's.  Returns ``(x, kept)``."""
    n, d = int(x.shape[-2]), int(x.shape[-1])
    limit = (6.0 / (n * d + n * (n + 2))) ** 0.5
    with mhc_scope():
        pre = op("mhc_pre",
                 {"X": x,
                  "Phi": param(f"{name}.phi", [n * d, n * (n + 2)],
                               "float32", init_mod.Uniform(-limit, limit)),
                  "Alpha": param(f"{name}.alpha", [3], "float32",
                                 init_mod.Constant(0.01)),
                  "Bias": vector(f"{name}.bias", n * (n + 2), 0.0)},
                 {"U": hp.dtype, "Post": "float32", "Res": "float32"},
                 {"sinkhorn_iters": int(hp.hc_sinkhorn_iters),
                  "eps": float(hp.hc_eps),
                  "clamp_min": float(hp.mhc_h_res_clamp_min),
                  "clamp_max": float(hp.mhc_h_res_clamp_max),
                  "rms_eps": float(hp.eps)})
    y, kept = sublayer(pre["U"])
    with mhc_scope():
        return op("mhc_post", {"X": x, "Y": y, "Post": pre["Post"],
                               "Res": pre["Res"]},
                  {"Out": hp.dtype})["Out"], kept


def shared(name):
    """A parameter the CURRENT program already holds, by name: what a
    second user of it takes (the embedding and the head that an MTP
    module shares with the main model), so that it is made and
    initialised once."""
    return default_main_program().global_block().var(name)


def mtp_module(h, next_ids, hp, prefix, block):
    """The multi-token-prediction module of DeepSeek-V3 (arXiv:2412.19437
    section 2.2; ``num_nextn_predict_layers`` 1), under the name scope
    ``mtp`` (its ops' own scope on the device trace, outside their
    group: the module's input, its two norms and its projection are
    ``mtp/embed``, its block's sublayers ``mtp/attn`` and so on)::

        h'_i = W_p [RMS_h(h_i) ; RMS_e(E[t_{i+1}])]      g_i = Block(h'_i)

    ``h`` [..., d]: the main model's last residual, BEFORE its final
    norm; ``next_ids`` int32, one a row of ``h``: the token that FOLLOWS
    the row's own; ``E`` is the main model's embedding ``{prefix}_emb``,
    which the program holds already (:func:`shared`).  ``block(x) ->
    (x, stats or None)``: ONE decoder block, the caller's (its kind of
    attention over its own cache).  Parameters: ``{prefix}_mtp_hnorm
    .scale`` / ``_enorm.scale`` and ``{prefix}_mtp_proj.w`` [2d, d] (rows
    ``0 .. d - 1`` take the hidden state's half).  Returns ``(g,
    stats)``; the draft logits of a row are :func:`mtp_logits` of it."""
    d = int(hp.hidden_size)
    with mtp_scope():
        with group("embed"):
            e = op("lookup_table", {"W": shared(f"{prefix}_emb"),
                                    "Ids": next_ids}, {"Out": hp.dtype},
                   {"is_sparse": False, "is_distributed": False,
                    "padding_idx": -1})["Out"]
            e = layers.reshape(e, shape=[int(n) for n in h.shape])
            both = layers.concat([rms(h, f"{prefix}_mtp_hnorm.scale", hp),
                                  rms(e, f"{prefix}_mtp_enorm.scale", hp)],
                                 axis=len(h.shape) - 1)
            x = layers.matmul(
                both, matrix(hp, f"{prefix}_mtp_proj.w", [2 * d, d]))
        return block(x)


def mtp_logits(g2, hp, prefix):
    """The draft logits of the MTP rows ``g2`` [R, d]: the module's own
    final norm ``{prefix}_mtp_norm.scale`` and the main model's head
    ``{prefix}_head.w`` (:func:`shared`); float32; ``mtp/head``."""
    with mtp_scope(), group("head"):
        h = rms(g2, f"{prefix}_mtp_norm.scale", hp)
        return op("matmul", {"X": h, "Y": shared(f"{prefix}_head.w")},
                  {"Out": "float32"}, {"out_dtype": "float32"})["Out"]


def draft_turn(hp, prefix, num_slots, draft_var, token, pos, lens,
               forward, mtp_block):
    """The decode TURN of a bundle whose MTP module drafts, two rows a
    slot (``ops/spec_ops.py``), behind the caller's :func:`decode_inputs`
    and caches: the committed token ``token`` at ``pos`` and, behind it,
    the slot's draft (the per-slot state ``draft_var`` [S, 1] int32,
    declared here).  One more feed, ``gen_spec`` [S, 1] int32: 0 turns a
    slot's draft row off (the row is dead: a blocking step that commits
    one token and returns the logits behind it).

    ``forward(rows) -> (x [S, 2, d], stats)``: the caller's embedding and
    main layers over ``spec_rows``' outputs (``Ids``, ``RowPos`` [S, 2];
    ``End`` [S, 1], ``RowLens`` [S * 2, 1]: a row sees the rows at or
    before its own), ``x`` the ONE residual its final norm takes.  The
    verify keeps the draft where it is the first row's own greedy pick;
    ``mtp_block(h, row_pos, end, row_lens) -> (g, stats)`` is the
    module's block over the kept rows (it fills its cache), and its last live row's pick
    is the slot's next draft.  Returns the fetches ``[logits [S, V] of
    the committed token's row, stats [n_moe + 1, 3], yield [S, 3]
    int32]``: a slot's (first token, second token or -1, how many: 0 for
    a free slot)."""
    S, d = int(num_slots), int(hp.hidden_size)
    draft = persistable(draft_var, [S, 1], "int32")
    with group("embed"):
        rows = op("spec_rows",
                  {"Token": token, "Draft": draft, "Pos": pos, "Lens": lens,
                   "On": data("gen_spec", [S, 1], "int32")},
                  {"Ids": "int32", "RowPos": "int32", "End": "int32",
                   "RowLens": "int32"}, {"max_len": int(hp.max_len)})
    x, stats = forward(rows)
    with group("head"):
        verdict = op("spec_verify",
                     {"Logits": logits(layers.reshape(x, shape=[S * 2, d]),
                                       hp, prefix),
                      "Ids": rows["Ids"], "RowLens": rows["RowLens"]},
                     {"Out": "int32", "NextIds": "int32", "MtpEnd": "int32",
                      "MtpRowLens": "int32", "First": "float32"})
    g, st = mtp_module(x, verdict["NextIds"], hp, prefix,
                       lambda h: mtp_block(h, rows["RowPos"],
                                           verdict["MtpEnd"],
                                           verdict["MtpRowLens"]))
    stats.append(st)
    with mtp_scope(), group("head"):
        last = op("spec_pick_row", {"X": g, "Verdict": verdict["Out"]},
                  {"Out": hp.dtype})["Out"]
        op("spec_draft", {"Logits": mtp_logits(last, hp, prefix),
                          "Lens": lens, "Draft": draft},
           {"DraftOut": draft})
    with group("head"):
        fetched_stats = layers.concat(stats, axis=0)
    return [verdict["First"], fetched_stats, verdict["Out"]]


def chunk_draft(x, first, last, slot, hp, prefix, num_slots, draft_var,
                block):
    """The MTP module's pass of a chunk program whose bundle drafts,
    behind the main layers: one more feed, ``gen_next_ids`` [1, C] int32,
    the token that FOLLOWS each row (the prompt shifted by one; -1 behind
    the prompt's last row, which takes the main model's own pick from
    ``first``, its logits there).  The module runs over the chunk's rows
    ``x`` [1, C, d] (``block``: its block over the slot's caches), so
    that its cache holds the prompt's rows too, and where the chunk
    holds the prompt's last row (``last``) its pick there becomes the
    slot's first draft (``draft_var``, declared here)."""
    draft = persistable(draft_var, [int(num_slots), 1], "int32")
    with group("head"):
        follows = op("spec_next_ids",
                     {"NextIds": data("gen_next_ids", [1, -1], "int32"),
                      "Logits": first}, {"Out": "int32"})["Out"]
    g, _ = mtp_module(x, follows, hp, prefix, block)
    with mtp_scope(), group("head"):
        op("spec_seed_draft",
           {"Logits": mtp_logits(last_row(g, last, hp), hp, prefix),
            "Last": last, "Slot": slot, "Draft": draft},
           {"DraftOut": draft})


def speculative_meta(draft_var):
    """``gen_meta.json``'s ``speculative`` of a bundle built on
    :func:`draft_turn`: a turn carries ``rows`` rows a slot and yields 1
    .. ``rows`` tokens; the draft lives in ``draft_var`` (a state
    array); ``feed`` turns a slot's draft row off."""
    return {"rows": 2, "draft_var": draft_var, "feed": "gen_spec"}


# ---------------------------------------------------------------------------
# the head and the tail of the three programs
# ---------------------------------------------------------------------------

def prefill_inputs(pos=True):
    """The feeds of a prefill over ONE prompt, length-dynamic (callers
    pad to a bucket): ``gen_ids`` [1, T] int32, ``gen_pos`` [1, T] int32
    (where the attention takes positions), ``gen_mask`` [1, T] f32 (1 =
    real token, real tokens first), ``gen_last`` [1, T] f32 (one-hot of
    the row whose logits are fetched).  Returns ``(ids, pos or None,
    mask, last)``."""
    ids = data("gen_ids", [1, -1], "int32")
    pos = data("gen_pos", [1, -1], "int32") if pos else None
    return ids, pos, data("gen_mask", [1, -1]), data("gen_last", [1, -1])


def last_row(x, last, hp):
    """The row of ``x`` [1, T, d] that the one-hot ``last`` names, as
    [1, d] (zeros where ``last`` is all zeros); group ``head``."""
    with group("head"):
        last3 = layers.cast(layers.reshape(last, shape=[1, 1, -1]),
                            hp.dtype)
        return layers.reshape(layers.matmul(last3, x),
                              shape=[-1, int(hp.hidden_size)])


def decode_inputs(num_slots, pos=True):
    """The feeds of a decode step: ``gen_token`` [S, 1] int32,
    ``gen_pos`` [S, 1] int32 (where the attention takes positions),
    ``gen_page_table`` [S, P] int32 (P bucketed by the predictor),
    ``gen_lens`` [S, 1] int32 (rows INCLUDING the current token; 0 = free
    slot: nothing is written).  Returns ``(token, pos or None, page
    table, lens)``."""
    S = int(num_slots)
    token = data("gen_token", [S, 1], "int32")
    pos = data("gen_pos", [S, 1], "int32") if pos else None
    return (token, pos, data("gen_page_table", [S, -1], "int32"),
            data("gen_lens", [S, 1], "int32"))


def decode_fetches(x, stats, num_slots, hp, prefix):
    """``[logits [S, V], stats [n_moe, 3]]`` from the rows ``x`` [S, 1,
    d] a slot and the expert layers' stats; no second fetch where no
    layer routes; group ``head``."""
    with group("head"):
        fetches = [logits(layers.reshape(
            x, shape=[int(num_slots), int(hp.hidden_size)]), hp, prefix)]
        if stats:
            fetches.append(layers.concat(stats, axis=0))
    return fetches


def train_inputs(seq_len, *rows):
    """The feeds ``gen_ids`` / ``gen_labels`` [1, T] int32 of a
    teacher-forced forward over ONE sequence, and the constant rows
    named in ``rows`` that the serving ops take as feeds: ``"pos"`` [1,
    T] (0 .. T-1), ``"mask"`` [1, T] (ones), ``"lens"`` [T, 1] (ones).
    Returns ``(ids, labels, {name: constant})``."""
    T = int(seq_len)
    ids = data("gen_ids", [1, T], "int32")
    labels = data("gen_labels", [1, T], "int32")
    values = {"pos": np.arange(T, dtype="int32").reshape(1, T),
              "mask": np.ones((1, T), "float32"),
              "lens": np.ones((T, 1), "int32")}
    made = {}
    for name in values:
        if name in rows:
            made[name] = layers.assign(values[name])
            made[name].stop_gradient = True
    return ids, labels, made


def train_loss(x, labels, hp, prefix):
    """``(avg_cost, feed_names)`` of a teacher-forced forward: the head
    over every row of ``x`` [1, T, d] and the mean cross entropy with
    ``labels`` [1, T]; group ``head``."""
    T = int(labels.shape[1])
    with group("head"):
        cost = layers.softmax_with_cross_entropy(
            logits(layers.reshape(x, shape=[T, int(hp.hidden_size)]), hp,
                   prefix),
            layers.reshape(labels, shape=[T, 1]))
        return layers.mean(x=cost), ["gen_ids", "gen_labels"]


# ---------------------------------------------------------------------------
# export: one parameter set -> prefill/ + decode/ + gen_meta.json
# ---------------------------------------------------------------------------

def write_model(dirname, program, feed_names, fetch_vars, executor):
    """The ``__model__`` + ``__params__`` pair ``io.load_inference_model``
    reads — written WITHOUT pruning (the decode program's in-place cache
    writes are load-bearing side effects a fetch-target prune would
    drop).  ``executor`` None: the ``__model__`` alone, of a program
    whose every persistable another program of the bundle brings."""
    from paddle_tpu import io as _io
    os.makedirs(dirname, exist_ok=True)
    model = {
        "program": program.to_dict(),
        "feed_var_names": list(feed_names),
        "fetch_var_names": [v.name for v in fetch_vars],
    }
    with open(os.path.join(dirname, "__model__"), "w") as f:
        json.dump(model, f)
    if executor is not None:
        _io.save_persistables(executor, dirname, program, "__params__")


def export_bundle(dirname, hp, where, build_prefill, build_decode,
                  cache_vars, n_layer, num_slots=8, prompt_buckets=None,
                  page_len=PAGE_LEN_DEFAULT, num_pages=None,
                  page_buckets=None, state_vars=None, sections=None,
                  more_programs=None):
    """Export a generation bundle: ``<dirname>/prefill/``,
    ``<dirname>/decode/`` (each a loadable inference model over ONE
    shared parameter set) and ``<dirname>/gen_meta.json`` describing the
    cache pool geometry.  The bundle's on-disk format is decided HERE and
    nowhere else.  Returns ``dirname``.

    The KV cache is a page pool: ``page_len`` rows per page (clamped to
    ``hp.max_len``), ``num_pages`` pool pages (default ``num_slots *
    ceil(max_len / page_len)`` — every slot can always grow to
    ``max_len``), ``page_buckets`` the declared page-count jit-signature
    ladder, ``prompt_buckets`` the prompt-length one (default
    ``lod.bucket_edges(1, max_len)``).

    ``build_prefill`` / ``build_decode``: ``(num_slots, page_len,
    num_pages) -> (feed names, fetch variables)``, each called under its
    own program guard, the prefill first: its startup program is the one
    that runs, and the decode program shares the initialised parameters
    by name.  ``cache_vars`` (page pools) and ``state_vars`` (per-slot
    state; None: the bundle's kind has none and its meta no such key)
    name the decode program's persistable caches: each starts as zeros
    of its variable's DECLARED type and shape, and only the decode
    program's ``__params__`` holds them (the prefill is written before
    they exist in the scope).  ``sections``: the kind's own meta keys,
    or ``(common meta) -> keys`` where they follow from the pool's
    geometry.  ``more_programs``: ``{directory: builder}`` of further
    programs over the SAME parameters and caches (a second shape of the
    chunk program), each built as the prefill is and written as a
    ``__model__`` alone; the kind's meta says what they are for.
    ``where`` names the caller in the post-export check's messages."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.lod import bucket_edges

    num_slots = int(num_slots)
    if prompt_buckets is None:
        prompt_buckets = bucket_edges(1, hp.max_len)
    page_len = max(1, min(int(page_len), int(hp.max_len)))
    pps = -(-int(hp.max_len) // page_len)
    num_pages = num_slots * pps if num_pages is None else int(num_pages)
    if page_buckets is None:
        page_buckets = default_page_buckets(pps)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        pre_main, pre_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(pre_main, pre_startup):
            pre_feeds, pre_fetches = build_prefill(num_slots, page_len,
                                                   num_pages)
        exe.run(pre_startup)
        write_model(os.path.join(dirname, "prefill"), pre_main,
                    pre_feeds, pre_fetches, exe)
        for name, build in (more_programs or {}).items():
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                feeds, fetches = build(num_slots, page_len, num_pages)
            write_model(os.path.join(dirname, name), main, feeds, fetches,
                        None)
        dec_main, dec_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec_main, dec_startup):
            dec_feeds, dec_fetches = build_decode(num_slots, page_len,
                                                  num_pages)
        # decode shares the ALREADY-initialized parameters (its startup
        # is never run); pools and per-slot state start as zeros
        block = dec_main.global_block()
        for name in list(cache_vars) + list(state_vars or []):
            var = block.var(name)
            scope.set_var(name, np.zeros(var.shape,
                                         jnp.dtype(str(var.dtype))))
        write_model(os.path.join(dirname, "decode"), dec_main,
                    dec_feeds, dec_fetches, exe)

    meta = {
        "format": "paddle_tpu.gen/1",
        "num_slots": num_slots,
        "max_len": int(hp.max_len),
        "vocab_size": int(hp.vocab_size),
        "n_layer": int(n_layer),
        "eos_id": int(hp.eos_id),
        "cache_vars": list(cache_vars),
        "prompt_buckets": [int(b) for b in prompt_buckets],
        "page_len": int(page_len),
        "num_pages": int(num_pages),
        "page_buckets": [int(b) for b in page_buckets],
        "page_table_feed": "gen_page_table",
    }
    if state_vars is not None:
        meta["state_vars"] = list(state_vars)
    meta.update(sections(meta) if callable(sections) else sections or {})
    with open(os.path.join(dirname, META_FILENAME), "w") as f:
        json.dump(meta, f, indent=2)
    # post-export contract (analysis/distributed.py): the bundle's
    # prefill/decode pair must satisfy the constant-jit-key contract
    # (static decode signature, cache geometry matching the meta,
    # prefill K/V fetches seeding exactly the cache) — a drifted
    # bundle fails HERE, at export, not at the first /generate;
    # unwarmable prompt buckets (the PTA018 recompile hazard) are
    # logged at warning level by the same check
    from paddle_tpu.analysis import verify_gen_bundle
    verify_gen_bundle(dirname, where=where)
    return dirname
