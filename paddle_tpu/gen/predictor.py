"""GenPredictor: the two-entry (prefill + decode) inference handle over
an exported generation bundle (``models/gen_lm.export_gen_model``).

The serving analog of :class:`paddle_tpu.serving.Predictor`, split along
the vLLM/Orca phase boundary:

* :meth:`prefill` runs one prompt (padded to a ``lod.row_bucket`` edge)
  through the full causal forward and returns the next-token logits plus
  the per-layer K/V rows that seed a cache slot.
* :meth:`decode_step` advances EVERY slot of the cache pool by one
  token.  The cache tensors are persistable state in the decode scope —
  they live on device across steps (the executor's donated in-place
  update path) and the step's feed signature is one of a declared few,
  so admission and eviction never change the jit key.
* :meth:`write_slot` / :meth:`clear_slot` are the (per-request, not
  per-token) slot writes that seed and reclaim cache rows: ONE compiled
  call over every cache array with the pools donated
  (:func:`_seed_pool`), fed the prefill's K/V as the device arrays the
  prefill executable produced — the K/V never visit the host and no
  pool is copied.

A bundle whose meta carries ``block_length`` L > 1
(``models/block_moe.py``) decodes BLOCKS of L rows
(``ops/block_ops.py``): a prefill runs the prompt and then mask rows to
the end of the block that the next position lies in, and a decode step
forwards the L rows of each slot's block (its committed tokens are a
``state_vars`` array) and, where the token fed completes that block, the
L masked rows of the next one in the same forward: every step yields
every live slot a token.

A bundle whose meta names ``state_vars`` (``models/hybrid_moe.py``)
keeps, beside the pool, a second kind of per-slot cache: arrays
``[num_slots, ...]`` that are not addressed through the page table (a
state-space layer's recurrent state and conv window).  The prefill
returns their values after the K/V, the same compiled call writes the
slot's row of each with the slot's pages, and the decode step reads and
writes them whole, in place.  What the meta holds decides; there is no
flag.

A bundle whose meta carries ``prefill_chunks`` (``models/window_moe.py``,
``models/latent_moe.py``) prefills a prompt as a SEQUENCE OF CHUNKS
(:meth:`GenPredictor.prefill_chunk`): each one compiled call, keyed by
(chunk rows, page bucket), that reads the slot's earlier rows from the
pools and the
per-slot state where the decode step reads them and writes its own rows
there, so nothing seeds the slot afterwards and the scheduler can run a
decode turn between two chunks.  :meth:`prefill` + :meth:`write_slot`
keep their contract on such a bundle through the same executables, on
borrowed pages.

``warmup`` declares BOTH signature families — every prefill bucket
(``Executor.warmup``) and the decode turn of every page bucket — plus one
seeding signature per prefill bucket, so a server flips ``/readyz`` with
the whole generation path compiled.

A decode TURN is one compiled call (:meth:`GenPredictor.dispatch_turn`).
The per-slot decode state — the token each slot feeds next, its position,
its rows and the whole ``[S, pages_per_slot]`` page table — lives on the
device beside the pools, donated like them, and the call advances it:
it slices the table to the step's page bucket (static: the jit key, one
executable a bucket), runs the decode program's step, picks each slot's
next token (the argmax, first index on ties) and adds one to the
position and the rows of every live slot.  The host keeps a numpy mirror
of that state for its bookkeeping and sends, as one argument of the same
call, a small int32 PATCH of the rows that an admission, an eviction or
an ending changed; a turn in which nothing changed passes the constant
"nothing" patch that already sits on the device.  What comes back to the
host is one small array a turn (:meth:`GenPredictor.read_turn`): the
``[S]`` ids and the step's ``decode_stats``.

A bundle whose meta carries ``speculative`` (``models/window_moe.py``
with its MTP module loaded; ``ops/spec_ops.py``) yields ONE OR TWO tokens
a slot a turn: the program forwards a slot's committed token and a draft
behind it, verifies the draft and drafts again, so the turn advances a
slot's position and rows by what its program yielded, which the host
learns at the read (``read_turn``: a run of tokens a slot).  The host's
mirror then holds which slots are live, no more; it sets a slot's row
when the slot is seated and when it leaves, and a slot that goes on is
the device's own.

The KV pool is ``[num_pages, page_len, H*D]`` pages addressed through a
per-slot page table (``page_len``, ``num_pages`` and ``page_buckets`` are
required keys of ``gen_meta.json``).  The predictor owns the page
allocator (:meth:`alloc_slot_pages` / :meth:`free_slot_pages`, driven by
the scheduler's admit/evict; a slot's changed row reaches the device in
the next turn's patch), slices the table to a declared ``page_buckets``
edge each step (the decode jit key is the bucket), and warms one decode
executable per bucket.  Decode reads scale with live prefix pages, not
``max_len``.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.obs.trace import span as _span

__all__ = ["GenPredictor", "is_gen_bundle"]

META_FILENAME = "gen_meta.json"


@functools.partial(jax.jit, donate_argnums=(0, 4),
                   static_argnames="max_rows")
@jax.named_scope("gen_seed")
def _seed_pool(pools, kv, idx, n, states=(), new_states=(), slot=0, *,
               max_rows):
    """The one way the KV pool and the per-slot state are written
    outside the decode step.

    ``pools``: every cache array, ``[N, unit, width]`` (pages of
    ``page_len`` rows; the row width is each array's own), DONATED — the
    update is in place.  ``kv``: one
    ``[1, bucket, width]`` array per pool (zeros on pad rows).
    ``states``: every per-slot state array ``[num_slots, ...]``, DONATED;
    ``new_states``: one ``[1, ...]`` value per state array, written whole
    as row ``slot`` unless ``n`` is 0.  Entry ``idx[j]`` of every pool, for
    ``j < n``, is written whole: rows ``j*unit ...`` of the first
    ``max_rows`` K/V rows, zeros past them — a re-used page carries no
    stale row.  ``idx`` has a fixed length and ``n`` is a traced trip
    count, so the signature depends on the prompt BUCKET alone, never
    on how many pages a request holds; ``n`` = 0 writes nothing.
    Returns the new pools followed by the new states, one flat tuple.
    Every instruction lies under the named scope ``gen_seed``: the
    executable's role in a device trace (docs/observability.md)."""
    def put_row(state, value):
        # only the slot's row is touched; with ``n`` = 0 it is rewritten
        # with itself
        row = jnp.where(n > 0, value.astype(state.dtype),
                        jax.lax.dynamic_slice_in_dim(state, slot, 1, 0))
        return jax.lax.dynamic_update_slice_in_dim(state, row, slot, 0)

    states = tuple(put_row(s, v) for s, v in zip(states, new_states))
    if not pools:
        return states
    unit = pools[0].shape[1]
    rows = min(kv[0].shape[1], max_rows)
    src_units = -(-rows // unit)
    src = [jnp.pad(a[0, :rows].astype(p.dtype),
                   ((0, src_units * unit - rows), (0, 0)))
           for p, a in zip(pools, kv)]

    def write_entry(j, pools):
        out = []
        for pool, rows_of in zip(pools, src):
            # past the K/V's rows the slice is clamped, then zeroed
            entry = jax.lax.dynamic_slice_in_dim(rows_of, j * unit, unit)
            entry = jnp.where(j < src_units, entry, 0)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                pool, entry[None], idx[j], 0))
        return tuple(out)

    return jax.lax.fori_loop(0, n, write_entry, tuple(pools)) + states


@functools.partial(jax.jit, static_argnames="rows")
def _slot_rows(pools, states, idx, n, slot, *, rows):
    """What :func:`_seed_pool` takes, read back out of the caches: of
    every pool the first ``rows`` rows of the pages ``idx`` as ``[1,
    rows, width]`` (zeros from row ``n`` on), then row ``slot`` of every
    state array as ``[1, ...]``.  One signature a ``rows``."""
    unit = pools[0].shape[1]
    keep = (jnp.arange(rows) < n)[None, :, None]
    kv = tuple(jnp.where(keep, pool[idx[:-(-rows // unit)]].reshape(
        1, -1, pool.shape[-1])[:, :rows], 0) for pool in pools)
    return kv + tuple(jax.lax.dynamic_slice_in_dim(s, slot, 1, 0)
                      for s in states)


# a turn's patch: one int32 row a slot, ``[S, _P_TABLE + pages_per_slot]``
# -- what of the slot's device-side decode state the host replaces
# before the step runs
_P_FLAGS, _P_TOKEN, _P_POS, _P_LENS, _P_TABLE = 0, 1, 2, 3, 4
# bits of _P_FLAGS: the row's position and rows are set; its table row is;
# the slot's draft row is off this turn (a bundle that drafts)
_SET_ROW, _SET_TABLE, _NO_DRAFT = 1, 2, 4


def _host_call(fn, *args):
    """``fn(*args)``, counted as ONE of the calls a decode turn costs the
    thread that makes it (``gen.decode.host_calls``): the launch of a
    compiled turn, a transfer to the device, a read from it.  Every such
    call of the decode path goes through here, so the counter sees one
    that comes back."""
    from paddle_tpu.profiler import runtime_metrics
    runtime_metrics.inc("gen.decode.host_calls")
    return fn(*args)


def _block_view(positions, lens, block_length):
    """A block bundle's step, from each slot's position and rows (numpy
    or traced arrays alike): ``(lens, fused, walk)``.  ``lens``: the rows
    the step is fed, rounded up to the end of the block that row
    ``lens - 1`` lies in (a step writes the block's rows whole);
    ``fused``: the slots whose token completes its block, so that the
    step stores the block and opens the next; ``walk``: the rows the
    step reads and writes through (``lens``, and the opened block)."""
    lens = -(-lens // block_length) * block_length
    fused = (positions + 1 == lens) & (lens > 0)
    return lens, fused, lens + block_length * fused


def _warm_entry(signature, run, compiled):
    """``run()``, and the :class:`~paddle_tpu.obs.perf.WarmupReport`
    bucket of what it compiled: ``compiled()`` counts the executables
    made so far."""
    from paddle_tpu.profiler import runtime_metrics
    size0 = compiled()
    hits0 = runtime_metrics.counter("compile_cache.hits")
    t0 = time.perf_counter()
    run()
    fresh = compiled() - size0
    hit = runtime_metrics.counter("compile_cache.hits") - hits0
    return {"signature": signature, "compiles": fresh,
            "seconds": time.perf_counter() - t0,
            "cache": ("warm" if fresh == 0 else
                      "persistent-hit" if hit > 0 else "cold")}


def is_gen_bundle(model_dir):
    """True when ``model_dir`` is a generation bundle (prefill + decode
    programs + ``gen_meta.json``) rather than a one-shot inference
    model."""
    return os.path.isfile(os.path.join(model_dir, META_FILENAME))


def _moe_trips(block):
    """``ops/moe_ops.trip_rows`` of every routed-expert op of a decode
    program, in program order (the order of its ``decode_stats`` rows)."""
    from paddle_tpu.ops.moe_ops import trip_rows
    return [trip_rows(int(np.prod(block.var(op.input("TopkIdx")[0]).shape)),
                      int(block.var(op.input("X")[0]).shape[-1]),
                      op.attr("chunk_rows"))
            for op in block.ops
            if op.type in ("moe_experts", "moe_experts_gated")]


class GenPredictor:
    """Load-once handle over a generation bundle; thread-compatible (one
    internal lock serializes executor access, mirroring Predictor)."""

    def __init__(self, model_dir):
        import paddle_tpu as fluid

        with open(os.path.join(model_dir, META_FILENAME)) as f:
            self.meta = json.load(f)
        self.num_slots = int(self.meta["num_slots"])
        self.max_len = int(self.meta["max_len"])
        self.vocab_size = int(self.meta["vocab_size"])
        self.eos_id = int(self.meta.get("eos_id", -1))
        self.cache_vars = list(self.meta["cache_vars"])
        # per-slot state that is not pages, and what the decode step
        # fetches beside the logits (both absent from a gen_lm bundle)
        self.state_vars = list(self.meta.get("state_vars") or ())
        self.decode_stats = list(self.meta.get("decode_stats") or ())
        # rows a slot a decode step (1: a token a slot a step), and the
        # token a masked row is fed
        self.block_length = int(self.meta.get("block_length") or 1)
        # learned sparse attention (``ops/dsa_ops.py``): ``top_k`` and the
        # number of layers that score and select; None without
        self.sparse_attention = self.meta.get("sparse_attention")
        # sliding-window layers beside full-attention ones
        # (``models/window_moe.py``): which layer keeps a ring a slot
        # (``state_vars``) and which pages; None without
        self.window_attention = self.meta.get("window_attention")
        # self-speculative decoding (``ops/spec_ops.py``): the rows a
        # slot's turn carries (the committed token and its drafts; 1
        # without) and yields at most
        self.speculative = self.meta.get("speculative")
        self.spec_rows = int((self.speculative or {}).get("rows", 1))
        # hyper-connections (``ops/mhc_ops.py``): the residual streams
        # and the wrappers a row passes; None without
        self.hyper_connections = self.meta.get("hyper_connections")
        # what the newest decode step's selections and the rows its two
        # kinds of layer read counted to (the step span's attributes)
        self.last_step_counts = {}
        self.mask_token_id = int(self.meta.get("mask_token_id", 0))
        self.prompt_buckets = [int(b) for b in self.meta["prompt_buckets"]]
        self.max_prompt_len = min(self.prompt_buckets[-1], self.max_len)
        # the rows of a prefill chunk, ascending, where the bundle's
        # prefill continues a slot's rows in place; () without
        self.prefill_chunks = sorted(
            int(c) for c in self.meta.get("prefill_chunks") or ())
        # a cross-decoder behind ONE shared pool
        # (``models/hybrid_decoder.py``): ``body`` names the chunk program
        # every chunk of a prompt but its last runs; None without
        self.cross_decoder = self.meta.get("cross_decoder")

        self._fluid = fluid
        self._scope = fluid.Scope()
        self._lock = threading.Lock()
        with fluid.scope_guard(self._scope):
            self._exe = fluid.Executor()
            (self._pre_prog, self._pre_feeds,
             self._pre_fetch) = fluid.io.load_inference_model(
                os.path.join(model_dir, "prefill"), self._exe)
            (self._dec_prog, self._dec_feeds,
             self._dec_fetch) = fluid.io.load_inference_model(
                os.path.join(model_dir, "decode"), self._exe)
            # the chunk program's second shape: a ``__model__`` alone
            # over the parameters and caches loaded above
            self._body = fluid.io.load_inference_model(os.path.join(
                model_dir, self.cross_decoder["body"]), self._exe) \
                if self.cross_decoder else None
        # load-time contract check (analysis/distributed.py): the
        # bundle's prefill/decode pair must satisfy the constant-jit-
        # key contract against gen_meta.json — a bundle that drifted
        # (hand-edited meta, mixed exports) fails HERE, before the
        # server ever flips /readyz, instead of recompiling per decode
        # step or seeding misshapen cache rows mid-request
        from paddle_tpu.analysis import (AnalysisResult,
                                         check_gen_bundle)
        AnalysisResult(check_gen_bundle(
            (self._pre_prog, self._pre_feeds, self._pre_fetch),
            (self._dec_prog, self._dec_feeds, self._dec_fetch),
            self.meta)).raise_on_errors(where="gen.GenPredictor")
        # the page pool's geometry, read once the check above has held
        # the meta to it (a bundle without pages does not get this far)
        self.page_len = int(self.meta["page_len"])
        self.num_pages = int(self.meta["num_pages"])
        self.page_buckets = [int(b) for b in self.meta["page_buckets"]]
        self.pages_per_slot = -(-self.max_len // self.page_len)
        # bytes one cached row takes over all cache arrays, each at its
        # own width and type (a K and a V row a layer, or one latent row)
        block = self._dec_prog.global_block()
        self.cache_row_bytes = sum(
            int(block.var(n).shape[-1])
            * jnp.dtype(str(block.var(n).dtype)).itemsize
            for n in self.cache_vars)
        # bytes ONE slot's rows of the per-slot state arrays take (a
        # mixer's recurrent state and conv window, a window layer's ring):
        # what an admission overwrites and an eviction gives up
        self.state_bytes_per_slot = sum(
            int(np.prod(block.var(n).shape[1:]))
            * jnp.dtype(str(block.var(n).dtype)).itemsize
            for n in self.state_vars)
        # the rows a trip of each expert layer's routed product takes,
        # in the order of the ``decode_stats`` rows: what
        # ``gen.moe.rows_carried`` is counted from
        self._moe_trips = _moe_trips(block) if self.decode_stats else []
        # host-side page allocator state (all mutated under _lock); the
        # table is the host's mirror of the device's, and ``_stale_rows``
        # the slots whose row the next turn's patch has to carry there
        self._page_table = np.zeros(
            (self.num_slots, self.pages_per_slot), np.int32)
        self._stale_rows = set()
        self._slot_pages = {}
        self._free_list = list(range(self.num_pages))
        # the decode turn (``_launch``): the device's decode state (token,
        # position, rows, page table: made at the first turn), the host's
        # mirror of its positions and rows, the constant patch of a turn
        # in which nothing changed, and one compiled turn a page bucket
        self._step = None
        self._dev_state = None
        self._dev_pos = np.zeros(self.num_slots, np.int32)
        self._dev_lens = np.zeros(self.num_slots, np.int32)
        self._no_patch = None
        self._turns = {}
        # HBM census: the KV pool (plus its host page table) is its own
        # collection, ``kv_pages``; weakref'd so a dropped predictor
        # releases cleanly
        import weakref
        from paddle_tpu.obs import perf as _perf
        ref = weakref.ref(self)

        def _kv_buffers():
            p = ref()
            if p is None:
                return ()
            return [v for v in (p._scope.find_var(n)
                                for n in p.cache_vars)
                    if v is not None and hasattr(v, "nbytes")] + \
                [p._page_table]

        def _state_buffers():
            p = ref()
            return () if p is None else [
                v for v in (p._scope.find_var(n) for n in p.state_vars)
                if v is not None and hasattr(v, "nbytes")]

        # a reloaded predictor must not leave a dead provider behind
        for collection, fn in (
                ("kv_pages", _kv_buffers), ("gen_state", _state_buffers)):
            weakref.finalize(self, _perf.unregister_hbm_provider,
                             _perf.register_hbm_provider(collection, fn))
        if self.window_attention:
            from paddle_tpu.profiler import runtime_metrics
            runtime_metrics.set_gauge("gen.window.ring_bytes",
                                      self.ring_bytes())
        # per-bucket constant prefill feeds (causal bias template)
        self._tri = {}
        # per-bucket static prefill FLOPs (analysis/cost): priced
        # lazily, consumed by GenScheduler's admission budget; a chunk's
        # by (chunk rows, page bucket)
        self._prefill_cost = {}
        self._chunk_cost = {}
        # a bundle with a cross-decoder: [prompt rows, cross-decoder
        # rows] of each slot's newest admission (``admitted_rows``)
        self._admitted = {}
        # clear_slot's zero rows, made once (device arrays)
        self._clear_kv = None
        # the newest blocking step's decode_stats array (None for a
        # bundle without one)
        self.last_decode_stats = None
        self._length_cost_fn = None

    # -- prefill -----------------------------------------------------------
    def _bucket(self, prompt_len):
        from paddle_tpu.lod import row_bucket
        b = row_bucket(prompt_len, edges=self.prompt_buckets)
        return min(b, self.max_len)

    def _cost_fn(self):
        """``flops(prompt_bucket)`` from the static cost model over the
        BUNDLE's actual prefill program (the ISSUE-15 wiring: admission
        weights and bucket planning price real programs, not guesses).
        Takes the predictor lock: the fit PROBES the prefill program by
        temporarily rewriting its feed var's length dim — that mutation
        must never interleave with another fit or a concurrent trace."""
        with self._lock:
            if self._length_cost_fn is None:
                from paddle_tpu.analysis import cost as _cost
                probe = (self.prompt_buckets[0],
                         max(self.prompt_buckets[-1],
                             self.prompt_buckets[0] + 1))
                self._length_cost_fn = _cost.row_cost_fn(
                    self._pre_prog, batch_var=self._pre_feeds[0],
                    dim=1, probe_rows=probe)
            return self._length_cost_fn

    def _page_write_cost(self, prompt_len):
        """Flop-equivalent of seeding a slot: every allocated
        prompt page of every cache array is written whole, at that
        array's own row width, and every state array's row for the slot
        — what admission budgets must see on top of the prefill
        forward."""
        pages = -(-max(int(prompt_len), 1) // self.page_len)
        block = self._dec_prog.global_block()
        rows = sum(int(block.var(n).shape[-1]) for n in self.cache_vars)
        state = sum(int(np.prod(block.var(n).shape[1:]))
                    for n in self.state_vars)
        return float(pages * self.page_len * rows + state)

    def prefill_cost(self, prompt_len):
        """Static FLOPs of prefilling a prompt of ``prompt_len`` tokens
        (priced at its padded bucket — what the device actually runs —
        plus the slot's page-seeding writes, so the memo is keyed by
        bucket and pages).  The GenScheduler weighs
        admissions with this so one decode iteration never stalls
        behind an unbounded prefill burst.  Cheap after the first call
        per (bucket, pages); the underlying fit is warmed by
        GenScheduler construction."""
        prompt_len = int(prompt_len)
        if self.prefill_chunks:
            # its chunks, each at its own page bucket; they write their
            # rows themselves: no seeding on top
            return sum(self.chunk_cost(a, b - a)
                       for a, b in self.chunk_spans(prompt_len))
        bucket = self._bucket(prompt_len)
        key = (bucket, -(-max(prompt_len, 1) // self.page_len))
        hit = self._prefill_cost.get(key)
        if hit is None:
            hit = float(self._cost_fn()(bucket)) + \
                self._page_write_cost(prompt_len)
            self._prefill_cost[key] = hit
        return hit

    def chunk_spans(self, prompt_len):
        """``[(start, stop), ...]``: the chunks a prompt of
        ``prompt_len`` tokens runs as, in order: the largest rung's rows
        each, the last one what is left."""
        top = self.prefill_chunks[-1]
        return [(a, min(a + top, prompt_len))
                for a in range(0, max(int(prompt_len), 1), top)]

    def _chunk_shape(self, start, n):
        """``(rows, pages)`` the chunk of ``n`` tokens at position
        ``start`` runs at: the smallest rung that holds it and the
        smallest page bucket that covers its last row and is no smaller
        than the rung itself (the jit key; :meth:`_chunk_shapes` has
        every one there is)."""
        rows = next((c for c in self.prefill_chunks if c >= n), None)
        if rows is None:
            raise ValueError(f"a chunk of {n} tokens exceeds the bundle's "
                             f"largest, {self.prefill_chunks[-1]} rows")
        if start + n > self.max_prompt_len:
            raise ValueError(
                f"a chunk that ends at row {start + n} lies past the "
                f"bundle's max prompt length {self.max_prompt_len}")
        return rows, self._pages_bucket(max(start + n, rows))

    def _pages_bucket(self, rows):
        """The smallest declared page bucket that covers ``rows`` rows
        (clamped to ``pages_per_slot``)."""
        from paddle_tpu.lod import row_bucket
        return min(row_bucket(-(-rows // self.page_len),
                              edges=self.page_buckets), self.pages_per_slot)

    def _chunk_shapes(self):
        """Every ``(rows, pages)`` a chunk can run at, in order: a rung
        over the page buckets from its own rows' to the longest
        prompt's.  What :meth:`warmup` compiles."""
        top = self._pages_bucket(self.max_prompt_len)
        return [(c, P) for c in self.prefill_chunks
                for P in sorted({min(int(b), self.pages_per_slot)
                                 for b in self.page_buckets})
                if self._pages_bucket(c) <= P <= top]

    def chunk_cost(self, start, n):
        """Static FLOPs of ONE prefill chunk of ``n`` tokens at position
        ``start``, priced at what the device runs: its rung's rows over
        its page bucket's (the memo's key).  What the scheduler's
        ``prefill_budget`` weighs a chunk with."""
        key = self._chunk_shape(start, n)
        hit = self._chunk_cost.get(key)
        if hit is None:
            from paddle_tpu.analysis import cost as _cost
            rows, pages = key
            block = self._pre_prog.global_block()
            # the feeds' dynamic dims: the page table's its bucket, every
            # other the chunk's rows
            shapes = {name: [d if d >= 0 else
                             pages if name == "gen_page_table" else rows
                             for d in block.var(name).shape]
                      for name in self._pre_feeds}
            with self._lock:
                hit = float(_cost.estimate_at(self._pre_prog,
                                              shapes).total_flops)
            self._chunk_cost[key] = hit
        return hit

    def plan_prompt_buckets(self, observed_lengths, max_edges=4):
        """Cost-optimal prompt buckets for an OBSERVED length
        distribution: ``lod.select_bucket_edges`` weighted by the
        prefill program's static FLOPs-per-bucket plus the candidate
        length's page-seeding writes.  Returns
        a sorted edge list (capped at the bundle's ``max_len``) an
        operator can bake into the next export's ``gen_meta.json``."""
        from paddle_tpu.lod import select_bucket_edges
        lengths = [min(max(int(n), 1), self.max_len)
                   for n in observed_lengths]
        base = self._cost_fn()

        def cost_of(n):
            return float(base(n)) + self._page_write_cost(n)

        return select_bucket_edges(lengths, max_edges=max_edges,
                                   cost_of=cost_of)

    def plan_page_buckets(self, observed_lengths, max_edges=4):
        """Cost-optimal page-count bucket edges for an OBSERVED
        prefix-length distribution: ``lod.select_bucket_edges`` over
        live page counts.  The paged kernel reads the LIVE pages whatever
        the bucket, so a step is priced once, at the observed mean
        length (``cost.estimate(paged_live_rows=)``), and a bucket adds what it
        really costs a step: the width of the page table fed.  Returns a
        sorted edge list an operator can bake into the next export's
        ``page_buckets``."""
        from paddle_tpu.analysis import cost as _cost
        from paddle_tpu.lod import select_bucket_edges
        lengths = [min(max(int(n), 1), self.max_len)
                   for n in observed_lengths]
        counts = [-(-n // self.page_len) for n in lengths]
        with self._lock:
            step = float(_cost.estimate(
                self._dec_prog, paged_live_rows=sum(lengths)
                / max(len(lengths), 1)).total_flops)

        def cost_of(pages):
            return step + 4.0 * self.num_slots * pages

        return select_bucket_edges(counts, max_edges=max_edges,
                                   cost_of=cost_of)

    # -- page allocator (driven by the scheduler) --------------------------
    @property
    def free_pages(self):
        """Unallocated pool pages."""
        with self._lock:
            return len(self._free_list)

    def pages_needed(self, prompt_len, max_new_tokens=1):
        """Pages a request must hold to decode to its length horizon
        WITHOUT mid-request allocation (allocation happens once, at
        admission — growth can never fail mid-decode).  A block bundle's
        horizon is the END of the block its last token lies in: every
        step writes a whole block's rows.  A bundle that drafts writes a
        draft's row behind the committed token's, and the turn that runs
        one step ahead of the host's knowledge of the stream's end writes
        both one row further: ``spec_rows - 1`` rows past the
        horizon."""
        horizon = int(prompt_len) + max(int(max_new_tokens), 1) \
            + self.spec_rows - 1
        horizon = min(self.max_len, self._block_end(horizon))
        return -(-max(horizon, 1) // self.page_len)

    def _block_end(self, rows):
        """``rows`` rounded up to a whole number of blocks."""
        return -(-rows // self.block_length) * self.block_length

    def _prefill_rows(self, bucket):
        """Rows of a prefill at ``bucket``: a block bundle's prefill runs
        up to one block of mask rows behind the prompt."""
        return bucket + (self.block_length if self.block_length > 1 else 0)

    def alloc_slot_pages(self, slot, n):
        """Assign ``n`` pool pages to ``slot`` (prefix order).  Raises
        ``RuntimeError`` when the pool cannot cover it — callers check
        :attr:`free_pages` first (admission backpressure)."""
        n = max(1, min(int(n), self.pages_per_slot))
        with self._lock:
            if slot in self._slot_pages:
                raise ValueError(f"slot {slot} already holds pages")
            if len(self._free_list) < n:
                raise RuntimeError(
                    f"page pool exhausted: slot {slot} needs {n} "
                    f"page(s), {len(self._free_list)} free")
            pages = [self._free_list.pop(0) for _ in range(n)]
            self._slot_pages[slot] = pages
            self._page_table[slot, :] = 0
            self._page_table[slot, :n] = pages
            self._stale_rows.add(slot)
            return list(pages)

    def free_all_pages(self):
        """Return EVERY slot's pages to the pool — the scheduler's
        crash-reset path, which discards all slots wholesale; returns
        the number of pages freed."""
        with self._lock:
            slots = list(self._slot_pages)
        return sum(self.free_slot_pages(s) for s in slots)

    def free_slot_pages(self, slot):
        """Return ``slot``'s pages to the free list (idempotent);
        returns the number freed.  The rows themselves are reclaimed
        lazily — re-allocation seeds pages via :meth:`write_slot`
        before any read addresses them."""
        with self._lock:
            pages = self._slot_pages.pop(slot, None)
            if not pages:
                return 0
            self._free_list.extend(pages)
            self._page_table[slot, :] = 0
            self._stale_rows.add(slot)
            return len(pages)

    def _prefill_feed(self, prompt, bucket):
        from paddle_tpu.lod import pad_to_bucket
        p, last_row = len(prompt), len(prompt) - 1
        if self.block_length > 1:
            # mask rows to the end of the block that position p lies in
            # (a whole masked block where p opens one); the logits are
            # row p's, which predict its own token
            prompt = list(prompt) + [self.mask_token_id] * (
                self._block_end(p + 1) - p)
            bucket, last_row, p = self._prefill_rows(bucket), p, len(prompt)
        mask = pad_to_bucket(np.ones((1, p), np.float32), bucket, axis=1)

        def ids():
            return pad_to_bucket(
                np.asarray(prompt, np.int32).reshape(1, p), bucket, axis=1)

        def pos():
            return np.arange(bucket, dtype=np.int32).reshape(1, bucket)

        def bias():
            tri = self._tri.get(bucket)
            if tri is None:
                tri = np.triu(np.full((bucket, bucket), -1e9, np.float32), 1)
                self._tri[bucket] = tri
            return (tri[None, None] + (mask * 1e9 - 1e9)[:, None, None, :]
                    ).astype(np.float32)

        def last():
            one_hot = np.zeros((1, bucket), np.float32)
            one_hot[0, last_row] = 1.0
            return one_hot

        # only what the bundle's prefill program declares is built (the
        # dense [b, b] bias is 4 MB at bucket 1024)
        make = {"gen_ids": ids, "gen_pos": pos, "gen_mask": lambda: mask,
                "gen_attn_bias": bias, "gen_last": last}
        return {k: make[k]() for k in self._pre_feeds}

    def can_resume(self, total_len):
        """True when a resumed stream of ``total_len`` tokens (original
        prompt + every token already emitted) still fits a prefill
        bucket — the admissibility gate for deterministic re-prefill
        failover.  A stream that has decoded past ``max_prompt_len``
        cannot be re-prefilled on this bundle (the serving handler
        replies a non-retryable ``resume_unsupported`` rather than a
        confusing prompt-length 400)."""
        return 0 < int(total_len) <= self.max_prompt_len

    def prefill(self, prompt):
        """Run one prompt (list/array of token ids); returns
        ``(logits [V], kv)``: ``logits`` on the host, ``kv`` the
        per-layer masked K/V list ``[k_0, v_0, ...]`` each
        ``[1, bucket, width]`` (zeros on pad rows), followed by one
        ``[1, ...]`` value per ``state_vars`` array (the state after the
        prompt's last token), as the DEVICE arrays
        the executable produced — :meth:`write_slot` takes them as they
        are; only the last token's logits cross to the host.  The
        prompt is padded to a declared bucket, so repeated lengths
        share one executable."""
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the bundle's "
                f"max prompt length {self.max_prompt_len}")
        if self.prefill_chunks:
            return self._prefill_by_chunks(prompt)
        feed = self._prefill_feed(prompt, self._bucket(len(prompt)))
        with self._lock:
            with self._fluid.scope_guard(self._scope):
                with _span("gen.prefill", tokens=len(prompt)):
                    outs = self._exe.run(self._pre_prog, feed=feed,
                                         fetch_list=self._pre_fetch,
                                         return_numpy=False)
                    logits = np.asarray(outs[0])[0]
        return logits, outs[1:]

    def _prefill_by_chunks(self, prompt):
        """:meth:`prefill` of a bundle whose prefill is a chunk program,
        through the SAME chunk executables an admission runs: it borrows
        a free slot (its per-slot state) and the pages the prompt takes
        from the free list, runs the prompt's chunks there, reads the
        rows back as the arrays :meth:`write_slot` takes (at the
        prompt's bucket: one signature a bucket) and hands slot and
        pages back.  Raises as :meth:`alloc_slot_pages` does where the
        pool cannot cover it, and where no slot is free.  For callers
        that hold no slot (a set-up check, a probe): the scheduler
        admits through :meth:`prefill_chunk`."""
        n = len(prompt)
        with self._lock:
            slot = next((i for i in range(self.num_slots)
                         if i not in self._slot_pages), None)
        if slot is None:
            raise RuntimeError("prefill: every slot is taken, none to "
                               "borrow for the prompt's chunks")
        pages = self.alloc_slot_pages(slot, -(-n // self.page_len))
        try:
            for a, b in self.chunk_spans(n):
                logits = self.prefill_chunk(
                    slot, prompt[a:b], a, after=prompt[b] if b < n else None,
                    final=b == n)
            idx = np.zeros(self.pages_per_slot, np.int32)
            idx[:len(pages)] = pages
            k = len(self.cache_vars)
            with self._lock:
                held = tuple(self._scope.find_var(name) for name in
                             self.cache_vars + self.state_vars)
                kv = _slot_rows(held[:k], held[k:], idx, np.int32(n),
                                np.int32(slot), rows=self._bucket(n))
        finally:
            self.free_slot_pages(slot)
        return np.asarray(logits)[0], list(kv)

    def prefill_chunk(self, slot, ids, start, after=None, final=True):
        """Run ONE CHUNK of ``slot``'s prompt: the tokens ``ids`` (no
        more than the largest of ``prefill_chunks``), which stand at
        positions ``start ..``; the chunks before it have run and the
        slot holds its pages (:meth:`alloc_slot_pages`).  ``after``: the
        prompt's token behind the chunk (None: the chunk ends the
        prompt), which a bundle that drafts needs: its MTP module's row
        takes the token that follows the row's own.  One compiled
        call, keyed by (chunk rows, page bucket), dispatched and NOT
        waited for: it reads the slot's rows ``0 .. start - 1`` from the
        pools and the per-slot state, as the decode step does, and
        writes its own rows there.  Returns the logits ``[1, V]`` of the
        chunk's last token, still on the device: the prompt's last chunk
        gives the first token.  ``final`` false (the prompt goes on
        behind this chunk) lets a bundle with a ``cross_decoder`` run its
        body program, which stops behind the pool's write: what comes
        back is then no logits and nobody's to read.  Such a bundle
        counts, always on, ``gen.admit.rows`` (prompt rows admitted) and
        ``gen.admit.cross_rows`` (rows its cross-decoder computed: one a
        prompt where the skip works), and :meth:`admitted_rows` keeps a
        slot's pair for the scheduler's ``gen.admit`` span.

        One ``gen.prefill`` span a call, carrying THIS chunk's
        ``tokens``, ``start``, ``rows`` (as run, pads included),
        ``pages`` and, with window layers, its own ``band_pairs`` /
        ``causal_pairs`` / ``*_key_blocks``, under learned sparse
        attention its own ``dsa_rows_scored`` / ``dsa_rows_selected``,
        under hyper-connections its ``mhc_rows``.
        Always-on:
        ``gen.prefill.chunks``, ``gen.prefill.rows`` (real) and
        ``gen.prefill.pad_rows``."""
        from paddle_tpu.lod import pad_to_bucket
        from paddle_tpu.profiler import runtime_metrics
        ids = np.asarray(ids, np.int32).reshape(1, -1)
        n, start = ids.shape[1], int(start)
        rows, pages = self._chunk_shape(start, n)
        last = np.zeros((1, rows), np.float32)
        last[0, n - 1] = 1.0
        feed = {"gen_ids": pad_to_bucket(ids, rows, axis=1),
                "gen_pos": start + np.arange(rows, dtype=np.int32)[None],
                "gen_mask": pad_to_bucket(np.ones((1, n), np.float32), rows,
                                          axis=1),
                "gen_last": last}
        if "gen_slot" in self._pre_feeds:   # a bundle with state a slot
            feed["gen_slot"] = np.full((1, 1), slot, np.int32)
        if "gen_next_ids" in self._pre_feeds:
            follows = np.append(ids[0, 1:], -1 if after is None else after)
            feed["gen_next_ids"] = pad_to_bucket(
                follows.astype(np.int32)[None], rows, axis=1)
        prog, _, fetch = self._body if self._body and not final \
            else (self._pre_prog, None, self._pre_fetch)
        with self._lock:
            # a copy: the call is not waited for, and the allocator
            # rewrites its table in place
            feed["gen_page_table"] = \
                self._page_table[slot:slot + 1, :pages].copy()
            with self._fluid.scope_guard(self._scope):
                with _span("gen.prefill", tokens=n, start=start, rows=rows,
                           pages=pages,
                           **self._chunk_pairs(start, n, rows, pages),
                           **self._chunk_selections(start, n),
                           **self._chunk_wrapped(n)):
                    logits, = self._exe.run(prog, feed=feed,
                                            fetch_list=fetch,
                                            return_numpy=False)
        if self.cross_decoder:
            # the last chunk's program runs the cross-decoder for ONE row
            cross = int(prog is self._pre_prog)
            if not start:
                self._admitted[slot] = [0, 0]
            seen = self._admitted.setdefault(slot, [0, 0])
            seen[0] += n
            seen[1] += cross
            runtime_metrics.inc("gen.admit.rows", n)
            runtime_metrics.inc("gen.admit.cross_rows", cross)
        runtime_metrics.inc("gen.prefill.chunks")
        runtime_metrics.inc("gen.prefill.rows", n)
        runtime_metrics.inc("gen.prefill.pad_rows", rows - n)
        return logits

    def admitted_rows(self, slot):
        """``{"rows", "cross_rows"}`` of the prompt ``slot`` was admitted
        with: its rows, and the rows the cross-decoder computed for it;
        {} for a bundle without a ``cross_decoder``."""
        rows = self._admitted.get(slot)
        return {"rows": rows[0], "cross_rows": rows[1]} if rows else {}

    def _chunk_wrapped(self, n):
        """A chunk of ``n`` real rows under hyper-connections:
        ``mhc_rows`` = rows x the wrappers each passes (two a block),
        what the wrappers' least bytes are counted from.  Span
        attributes; {} without."""
        hc = self.hyper_connections
        return {"mhc_rows": n * int(hc["wrappers"])} if hc else {}

    def _chunk_selections(self, start, n):
        """A chunk of ``n`` real rows at positions ``start ..`` under
        learned sparse attention: the query row at position ``t`` of
        every indexer scores ``t + 1`` rows and selects ``min(t + 1,
        top_k)`` of them (a prompt's chunks sum to its whole triangle).
        Span attributes; {} without."""
        sp = self.sparse_attention
        if not sp:
            return {}

        def through(m):     # (scored, selected) of rows 0 .. m - 1
            k = min(int(sp["top_k"]), m)
            return m * (m + 1) // 2, k * (k + 1) // 2 + (m - k) * k

        (s1, k1), (s0, k0) = through(start + n), through(start)
        return {"dsa_rows_scored": sp["indexers"] * (s1 - s0),
                "dsa_rows_selected": sp["indexers"] * (k1 - k0)}

    def _chunk_pairs(self, start, n, rows, pages):
        """A chunk of ``n`` real rows at positions ``start ..``, run as
        ``rows`` rows over ``pages`` pages, through window and full
        layers: the (query row, key row) pairs ONE window layer's band
        and ONE full layer's causal trapezium hold for THESE rows (not
        the prompt's whole triangle: the chunks' sum to it), and the key
        blocks the two prefill kernels compute a layer for the chunk (0
        where the composed form runs).  Span attributes; {} without
        window layers."""
        win = self.window_attention
        if not win:
            return {}
        from paddle_tpu.ops.window_ops import key_blocks_computed
        w = int(win["window"])

        def band(m):    # the band's pairs of rows 0 .. m - 1
            return min(w, m) * (min(w, m) + 1) // 2 + max(m - w, 0) * w

        out = {"band_pairs": band(start + n) - band(start),
               "causal_pairs": n * start + n * (n + 1) // 2,
               "window_layers": len(win["layers"]),
               "full_layers": len(win["full_layers"])}
        for attr, heads, window in (
                ("band_key_blocks", win.get("heads"), w),
                ("causal_key_blocks", win.get("full_heads"), 0)):
            if heads:
                h, hkv = heads
                out[attr] = hkv * key_blocks_computed(
                    rows, h // hkv, window, start,
                    keys=pages * self.page_len)[0]
        return out

    def _count_window_rows(self, lens):
        """The rows a decode step's two kinds of layer read, from the rows
        its slots hold: a full layer every live row, a window layer no
        more than ``window`` of them (its ring).  Counted always-on
        (``gen.window.*``) and kept for the step's span; {} without
        window layers."""
        win = self.window_attention
        if not win:
            return {}
        from paddle_tpu.profiler import runtime_metrics
        rows = lens[lens > 0].astype(np.int64)
        live = int(rows.sum())
        n_win, n_full = len(win["layers"]), len(win["full_layers"])
        in_window = int(np.minimum(rows, int(win["window"])).sum())
        out = {"full_rows": n_full * live, "window_rows": n_win * in_window,
               # what the step would read with every layer paged by length
               "all_rows": (n_full + n_win) * live,
               "ring_bytes": sum(int(b) for b in win["row_bytes"])
               * in_window}
        runtime_metrics.inc("gen.window.rows_read", out["window_rows"])
        runtime_metrics.inc("gen.window.rows_saved",
                            n_win * (live - in_window))
        return out

    def ring_bytes(self):
        """Bytes the window layers' rings hold over all slots: a
        constant of the bundle (0 without window layers)."""
        win = self.window_attention
        if not win:
            return 0
        return self.num_slots * int(win["ring"]) * sum(
            int(b) for b in win["row_bytes"])

    def _count_selections(self, lens):
        """A decode step's selections, from the rows its slots hold: one
        a live slot an indexer, over ``lens`` rows, ``min(lens, top_k)``
        of them kept (all of them, the identity, up to ``top_k``).
        Counted always-on (``gen.dsa.*``) and kept for the step's span;
        {} without learned sparse attention."""
        sp = self.sparse_attention
        if not sp:
            return {}
        from paddle_tpu.profiler import runtime_metrics
        rows = lens[lens > 0].astype(np.int64)
        n, k = int(sp["indexers"]), int(sp["top_k"])
        out = {"dsa_rows_scored": n * int(rows.sum()),
               "dsa_rows_selected": n * int(np.minimum(rows, k).sum()),
               "dsa_selections": n * int(rows.size),
               "dsa_identity_selections": n * int((rows <= k).sum())}
        runtime_metrics.inc("gen.dsa.rows_scored", out["dsa_rows_scored"])
        runtime_metrics.inc("gen.dsa.rows_selected",
                            out["dsa_rows_selected"])
        runtime_metrics.inc("gen.dsa.selections", out["dsa_selections"])
        runtime_metrics.inc("gen.dsa.identity_selections",
                            out["dsa_identity_selections"])
        return out

    # -- cache-slot lifecycle (per request) --------------------------------
    def _write_pool(self, kv, idx, n, slot=0):
        """Entries ``idx[:n]`` of every cache array <- ``kv``'s rows,
        then zeros, and row ``slot`` of every state array <- the values
        that follow the K/V in ``kv`` (:func:`_seed_pool`); caller holds
        ``_lock``.  Returns the device-resident pool and state arrays
        the call had to COPY (their old buffer outlived the donation):
        0 unless in-place seeding broke."""
        names = self.cache_vars + self.state_vars
        old = tuple(self._scope.find_var(name) for name in names)
        k = len(self.cache_vars)
        new = _seed_pool(old[:k], tuple(kv[:k]), idx, np.int32(n), old[k:],
                         tuple(kv[k:]), np.int32(slot),
                         max_rows=self.max_len)
        for name, arr in zip(names, new):
            self._scope.set_var(name, arr)
        return sum(1 for a in old
                   if isinstance(a, jax.Array) and not a.is_deleted())

    def _seed_slot(self, slot, kv):
        """``slot``'s allocated pages of the pool <- ``kv`` (caller holds
        ``_lock``); None before ``alloc_slot_pages``.  Returns what
        :meth:`_write_pool` does; ``gen.seed.eager_ops`` counts it."""
        from paddle_tpu.profiler import runtime_metrics
        pages = self._slot_pages.get(slot)
        if not pages:
            return None
        idx = np.zeros(self.pages_per_slot, np.int32)
        idx[:len(pages)] = pages
        copied = self._write_pool(kv, idx, len(pages), slot)
        runtime_metrics.inc("gen.seed.compiled_calls")
        runtime_metrics.inc("gen.seed.eager_ops", copied)
        return copied

    def _zero_kv(self, bucket):
        """All-zero K/V and state values shaped like one ``bucket``'s
        prefill outputs, each cache array's at its own row width and type
        (what ``clear_slot`` and ``warmup`` seed with), committed to the
        executor's device as those are — a differently placed argument
        would be a second jit signature; transferred, not computed, so
        making them compiles nothing."""
        device = self._exe.place.jax_device()
        k = len(self.cache_vars)
        made = {}

        def zeros(var, shape):
            key = (tuple(shape), str(var.dtype))
            if key not in made:
                made[key] = jax.device_put(
                    np.zeros(shape, jnp.dtype(str(var.dtype))), device)
            return made[key]

        # a chunk prefill fetches no K/V: its caches ARE the decode
        # program's, and so are their widths and types
        block = self._dec_prog.global_block()
        like = [block.var(n) for n in self.cache_vars + self.state_vars] \
            if self.prefill_chunks else self._pre_fetch[1:]
        return [zeros(v, (1, self._prefill_rows(bucket), int(v.shape[-1])))
                for v in like[:k]] + \
               [zeros(v, (1,) + tuple(int(d) for d in v.shape[1:]))
                for v in like[k:]]

    def write_slot(self, slot, kv, prompt_len):
        """Seed cache slot ``slot`` with a prefill's K/V rows.

        One compiled call for all ``2 * n_layer`` cache arrays, pools
        donated: the K/V stay on the device and the pools are updated
        in place.  The slot's ALLOCATED pages are written whole (prompt
        rows + zero fill — re-used pages carry no stale rows).  Returns
        the number of pool arrays that were copied instead (0 when
        healthy)."""
        with self._lock:
            copied = self._seed_slot(slot, kv)
        if copied is None:
            raise RuntimeError(
                f"write_slot({slot}) before alloc_slot_pages")
        return copied

    def clear_slot(self, slot):
        """Zero a reclaimed slot's cache rows — the same compiled call
        as :meth:`write_slot`, fed zero rows of the smallest prompt
        bucket.  Not strictly required — admission seeds every
        re-allocated page — but keeps a freed slot from pinning stale
        request data."""
        with self._lock:
            if self._clear_kv is None:
                self._clear_kv = self._zero_kv(
                    min(self.prompt_buckets[0], self.max_len))
            self._seed_slot(slot, self._clear_kv)

    # -- decode ------------------------------------------------------------
    def _compiled_turn(self, pages):
        """The compiled turn of page bucket ``pages``: ``turn(state,
        patch, ro, inout, key) -> ((state, logits, read), written)`` over
        the decode program's step (``Executor.compiled_step``, inlined:
        the op scopes keep their names), ``state`` and ``inout`` donated.
        Every bucket's turn is the same function but for the static width
        of the table slice.  The turn's own instructions, around the
        step's, lie under the named scope ``gen_turn``."""
        fn = self._turns.get(pages)
        if fn is not None:
            return fn
        if self._step is None:
            self._step = self._exe.compiled_step(
                self._dec_prog, self._dec_feeds, self._dec_fetch,
                self._scope)
        step, S, L = self._step, self.num_slots, self.block_length
        block = self._dec_prog.global_block()
        kinds = {n: jnp.dtype(str(block.var(n).dtype))
                 for n in self._dec_feeds}
        with_stats = bool(self.decode_stats)
        drafts = self.speculative is not None

        def turn(state, patch, ro, inout, key):
            with jax.named_scope("gen_turn"):
                token, pos, lens, table = state
                flags = patch[:, _P_FLAGS, None]
                set_row = (flags & _SET_ROW) > 0
                token = jnp.where(patch[:, _P_TOKEN, None] >= 0,
                                  patch[:, _P_TOKEN, None], token)
                pos = jnp.where(set_row, patch[:, _P_POS, None], pos)
                lens = jnp.where(set_row, patch[:, _P_LENS, None], lens)
                table = jnp.where((flags & _SET_TABLE) > 0,
                                  patch[:, _P_TABLE:], table)
                feeds = {"gen_token": token, "gen_pos": pos,
                         "gen_page_table": table[:, :pages],
                         "gen_lens": _block_view(pos, lens, L)[0] if L > 1
                         else lens}
                if drafts:
                    feeds["gen_spec"] = (flags & _NO_DRAFT) == 0
                feeds = {n: feeds[n].astype(kinds[n]) for n in kinds}
            (logits, *stats), written = step.flat(feeds, ro, inout, key)
            with jax.named_scope("gen_turn"):
                if drafts:
                    # the program verified its own draft: a slot's (first
                    # token, second or -1, how many); the last of them is
                    # the committed token the next turn feeds
                    *stats, verdict = stats
                    count = verdict[:, 2:]
                    token = jnp.where(count > 1, verdict[:, 1:2],
                                      verdict[:, :1])
                    read = jnp.concatenate(
                        [verdict.astype(jnp.int32).reshape(-1)]
                        + [s.astype(jnp.int32).reshape(-1) for s in stats])
                    return ((token, pos + count, lens + count, table),
                            logits, read), written
                # the greedy pick: first index on ties, as np.argmax
                ids = jnp.argmax(logits.reshape(S, -1), axis=-1
                                 ).astype(jnp.int32)
                read = ids if not with_stats else jnp.concatenate(
                    [ids, stats[0].astype(jnp.int32).reshape(-1)])
                live = (lens > 0).astype(jnp.int32)
                return ((ids[:, None], pos + live, lens + live, table),
                        logits, read), written

        fn = jax.jit(turn, donate_argnums=(0, 3))
        from paddle_tpu.obs import perf as _perf
        if _perf.capture_enabled():
            # the cost / memory record of the bucket's executable
            # (``paddle_tpu profile compile``), as ``Executor.run``'s
            fn = _perf.instrument_jit(
                fn, label=f"gen_turn:gen_page_table:{S}x{pages}")
        self._turns[pages] = fn
        return fn

    def _patch(self, tokens, positions, lens, every_row, no_draft=False):
        """The turn's patch (numpy), or None where nothing changed: the
        rows whose position or rows differ from what the device holds
        (all of them with ``every_row``), the tokens the host sets
        (``tokens`` >= 0) and the table rows the allocator changed since
        the last turn.  ``no_draft``: every slot's draft row is off this
        turn (a bundle that drafts; it travels with ``every_row``).
        Caller holds ``_lock``."""
        if every_row:
            changed = np.ones(self.num_slots, bool)
        elif self.speculative:
            # the device advances a slot by what its program yielded: the
            # host sets a row where it seats a stream (its token is the
            # host's) and where a slot left
            changed = (tokens >= 0) | ((lens > 0) != (self._dev_lens > 0))
        else:
            changed = (positions != self._dev_pos) | (lens != self._dev_lens)
        # a slot that holds pages and no live row is being ADMITTED chunk
        # by chunk (its chunks are fed the host's table): its row
        # travels once, with the turn that seats it
        stale = {slot for slot in self._stale_rows
                 if lens[slot] > 0 or slot not in self._slot_pages}
        if not (changed.any() or stale or (tokens >= 0).any()):
            return None
        patch = np.zeros((self.num_slots, _P_TABLE + self.pages_per_slot),
                         np.int32)
        patch[:, _P_FLAGS] = _SET_ROW * changed + _NO_DRAFT * bool(no_draft)
        patch[:, _P_TOKEN] = tokens
        patch[:, _P_POS] = positions
        patch[:, _P_LENS] = lens
        for slot in stale:
            patch[slot, _P_FLAGS] |= _SET_TABLE
            patch[slot, _P_TABLE:] = self._page_table[slot]
        self._stale_rows -= stale
        return patch

    def _launch(self, tokens, positions, lens, every_row=False, pages=None,
                no_draft=False):
        """Dispatch one decode turn, not waited for: ``(logits, read,
        patched)``, the first two as the device arrays the executable
        will fill (``read``: the ``[S]`` ids it picked, then the step's
        ``decode_stats``, flat), ``patched`` whether the host changed
        anything.

        ``tokens``: int32 ``[S]``, the token a slot is fed where the host
        holds it, -1 where it is the device's own pick from the turn
        before.  ``positions`` / ``lens``: what the step is to run at;
        only where they differ from what the device's state has advanced
        to (or everywhere, with ``every_row``) do they travel, in the
        patch.  ONE compiled call either way, and no transfer outside it:
        a numpy patch is an argument of the call.  ``pages``: the page
        bucket, where it is not to follow from ``lens`` (the warm-up's).

        The ``gen.decode.stall`` failpoint fires INSIDE the lock: a
        ``delay`` action models per-iteration device time serialized per
        replica (the decode bench's cost model), an ``error`` a device
        fault in the decode step."""
        from paddle_tpu.fault import chaos
        from paddle_tpu.obs import trace as _trace
        from paddle_tpu.profiler import runtime_metrics
        S, L = self.num_slots, self.block_length
        fed = walk = lens
        if L > 1:
            # slots whose token completes its block: the step stores the
            # block and opens the next, whose rows the kernel walks too
            fed, fused, walk = _block_view(positions, lens, L)
            live = int(np.count_nonzero(lens))
            n_fused = int(np.count_nonzero(fused))
            runtime_metrics.inc("gen.block.forwards", live)
            runtime_metrics.inc("gen.block.fused", n_fused)
            runtime_metrics.inc("gen.block.rows", (live + n_fused) * L)
        if pages is None:
            # a draft's row lies behind the committed token's
            pages = self._page_bucket(
                walk + (self.spec_rows - 1) * (walk > 0) * (not no_draft))
        self.last_step_counts = {**self._count_selections(fed),
                                 **self._count_window_rows(fed)}
        with self._lock:
            chaos.fire("gen.decode.stall", slots=S)
            t0 = time.perf_counter()
            if self._dev_state is None:
                device = self._exe.place.jax_device()
                self._dev_state = tuple(
                    _host_call(jax.device_put, np.zeros(shape, np.int32),
                               device)
                    for shape in ((S, 1), (S, 1), (S, 1),
                                  self._page_table.shape))
                nothing = np.zeros(
                    (S, _P_TABLE + self.pages_per_slot), np.int32)
                nothing[:, _P_TOKEN] = -1
                # uncommitted, as a numpy patch is to ``jit``: the two
                # kinds of turn are one executable
                self._no_patch = _host_call(jnp.asarray, nothing)
            turn, patch = self._compiled_turn(pages), None

            def feed():     # the launch's ``executor.feed``
                nonlocal patch
                patch = self._patch(tokens, positions, lens, every_row,
                                    no_draft)
                return (self._dev_state,
                        self._no_patch if patch is None else patch)

            with self._fluid.scope_guard(self._scope):
                self._dev_state, logits, read = self._step.call(
                    functools.partial(_host_call, turn), feed)
            # (a bundle that drafts advances a slot by its program's
            # yield: of its mirror only which slots are live is read)
            advance = (lens > 0).astype(np.int32)
            self._dev_pos = positions + advance
            self._dev_lens = lens + advance
            _trace.record_span("gen.dispatch", t0, time.perf_counter() - t0,
                               parent_id=_trace.current_span_id(),
                               patched=int(patch is not None), pages=pages)
        return logits, read, patch is not None

    def dispatch_turn(self, tokens, positions, lens):
        """The scheduler's side of a decode turn: advance EVERY slot of
        the pool by one token, dispatched and not waited for.  ``tokens``
        / ``positions`` / ``lens``: int32 ``[S]`` as :meth:`_launch`
        takes them (zeros for a free slot; ``tokens`` -1 where the slot
        goes on from the device's own pick).  Returns the turn's small
        result, still on the device, for :meth:`read_turn`; the step's
        counts (selections, window rows) are in ``last_step_counts``.

        Always-on: ``gen.decode.turns_steady`` (nothing changed: the
        call took no array from the host but the RNG key) or
        ``gen.decode.turns_patched``; and ``gen.decode.host_calls``,
        counted where each is made (:func:`_host_call`): the launches of
        a compiled turn, the transfers that put the decode state on the
        device, and the reads of a turn's result (a blocking
        ``decode_step`` reads the logits too)."""
        from paddle_tpu.profiler import runtime_metrics
        _, read, patched = self._launch(np.asarray(tokens, np.int32),
                                        np.asarray(positions, np.int32),
                                        np.asarray(lens, np.int32))
        runtime_metrics.inc("gen.decode.turns_patched" if patched
                            else "gen.decode.turns_steady")
        return read

    def read_turn(self, read):
        """Wait for a dispatched turn and read it, in ONE transfer:
        ``(ids, counts)``, the token every slot's row yielded (a list of
        ``S``; of a bundle that drafts, the RUN of tokens every slot
        yielded, in order: one or two, none for a free slot) and the
        step's ``decode_stats`` columns reduced and counted
        (:meth:`count_decode_stats`; {} without)."""
        ids, stats = self._split_read(_host_call(np.asarray, read))
        if self.speculative:
            ids = [row[:n] for row, n in zip(ids[:, :-1].tolist(),
                                             ids[:, -1].tolist())]
        else:
            ids = ids.tolist()
        return ids, {} if stats is None else self.count_decode_stats(stats)

    def _split_read(self, read):
        """A turn's read as ``(ids [S], stats or None)``; of a bundle
        that drafts ``ids`` is ``[S, spec_rows + 1]``: a slot's tokens,
        then how many of them it yielded."""
        S = self.num_slots
        n = S * (self.spec_rows + 1) if self.speculative else S
        ids, rest = read[:n], read[n:]
        if self.speculative:
            ids = ids.reshape(S, -1)
        if not self.decode_stats:
            return ids, None
        return ids, rest.reshape(-1, len(self.decode_stats))

    def decode_step(self, tokens, positions, lens):
        """One BLOCKING decode iteration over the whole slot pool: for
        every live slot the token at ``positions``, the logits for the
        position after it.  The same compiled turn as the scheduler's
        (:meth:`dispatch_turn`) with every slot's row in the patch, then
        the read of its result and of the logits.

        ``tokens``/``positions``: int32 ``[S]`` (zeros for free slots).
        ``lens``: int32 ``[S]`` prefix rows INCLUDING the current token
        (0 = free slot: its pages are never touched) — the page table
        is sliced to the smallest declared page bucket covering
        ``max(lens)``, so the jit key is the bucket.  Returns logits
        ``[S, V]``.

        A block bundle (``block_length`` L > 1) forwards the L rows of
        the block that row ``lens - 1`` lies in (``lens`` is rounded up
        to that block's end in the turn: a step writes the block's rows
        whole), commits the token at ``positions`` there, and returns the
        logits of the block's leftmost masked row.  A token that
        completes its block (``positions + 1`` a multiple of L) makes it
        the forward that stores the block's K/V, and the SAME forward
        carries the next block with every row masked (the program takes
        ``2L`` rows a slot; the page bucket covers ``lens + L`` for such
        a slot), so the logits are always those for ``positions + 1``.
        This call raises where such a slot holds too few pages for the
        block it opens; the scheduler's allocation
        (:meth:`pages_needed`) covers it.

        A bundle that drafts (``speculative``) runs this step with its
        draft rows OFF: the token at ``positions`` is committed, the
        logits are those for ``positions + 1``, and its MTP module
        still fills its own cache row and drafts.

        A bundle with ``decode_stats`` computes, with the logits, one
        small int32 array ``[n, len(decode_stats)]`` a step; each column's
        sum (or max, as the meta says) goes on the ``gen.decode_step``
        span under the column's name, with ``live`` (live slots), and is
        counted always-on: ``gen.<name with its first _ as a .>``, a
        counter for a sum and a histogram for a max.  The array is kept
        in ``last_decode_stats``.

        A bundle with ``window_attention`` (``models/window_moe.py``)
        counts the rows its two kinds of layer read (``_count_window_rows``):
        ``full_rows``, ``window_rows`` and ``ring_bytes`` on the span,
        ``gen.window.rows_read`` / ``rows_saved`` always-on.

        A bundle with ``sparse_attention`` (``ops/dsa_ops.py``) counts
        the step's selections from ``lens`` (``_count_selections``):
        ``dsa_rows_scored``, ``dsa_rows_selected``, ``dsa_selections``,
        ``dsa_identity_selections`` on the span and as ``gen.dsa.*``."""
        S = self.num_slots
        tokens = np.asarray(tokens, np.int32).reshape(S)
        positions = np.asarray(positions, np.int32).reshape(S)
        lens = np.asarray(lens, np.int32).reshape(S)
        if self.block_length > 1:
            self._check_opened_pages(
                *_block_view(positions, lens, self.block_length)[1:])
        with _span("gen.decode_step") as step:
            # a bundle that drafts commits the one token here: its draft
            # rows are off, and the logits are the committed token's
            logits, read, _ = self._launch(
                tokens, positions, lens, every_row=True,
                no_draft=self.speculative is not None)
            _, stats = self._split_read(_host_call(np.asarray, read))
            self.last_decode_stats = stats
            step.set(**self.last_step_counts)
            if stats is not None:
                step.set(live=int(np.count_nonzero(lens)),
                         **self.count_decode_stats(stats))
            return _host_call(np.asarray, logits)

    def _check_opened_pages(self, fused, walk):
        """Raise where a slot whose step opens a block (``fused``) holds
        fewer pages than ``walk``, the rows through that block's end."""
        with self._lock:
            for slot in np.flatnonzero(fused):
                held = len(self._slot_pages.get(int(slot), ()))
                if held * self.page_len < int(walk[slot]):
                    raise RuntimeError(
                        f"slot {int(slot)} holds {held} page(s): too few "
                        f"to open the block behind row "
                        f"{int(walk[slot]) - self.block_length}")

    def count_decode_stats(self, stats):
        """A step's ``decode_stats`` array with its columns reduced over
        their rows: counted always-on, returned for the
        ``gen.decode_step`` span."""
        from paddle_tpu.profiler import runtime_metrics
        stats, out = np.asarray(stats), {}
        for j, col in enumerate(self.decode_stats):
            metric = "gen." + col["name"].replace("_", ".", 1)
            if col.get("reduce") == "max":
                out[col["name"]] = int(stats[:, j].max())
                runtime_metrics.bucket(metric, out[col["name"]])
            else:
                out[col["name"]] = int(stats[:, j].sum())
                runtime_metrics.inc(metric, out[col["name"]])
        if len(self._moe_trips) == len(stats):
            # how full the routed products' trips were: the sorted rows
            # that held an assignment over the rows the trips moved
            from paddle_tpu.ops.moe_ops import rows_carried
            runtime_metrics.inc("gen.moe.rows_landed", int(stats[:, 0].sum()))
            runtime_metrics.inc("gen.moe.rows_carried", sum(
                rows_carried(n, chunk)
                for n, chunk in zip(stats[:, 0].tolist(), self._moe_trips)))
        return out

    def _page_bucket(self, rows):
        """The step's page bucket: the smallest declared one covering the
        longest live prefix (clamped to ``pages_per_slot`` —
        ``row_bucket`` past the declared ladder falls back to its
        power-of-two ladder, which must never widen the jit key beyond
        the pool).  ``rows``: int32 ``[S]``, the rows each slot's step
        reads and writes through (for a block bundle's slot that opens
        its next block, more than the ``lens`` it is fed).  Observes the
        paged counters (``gen.paged.*``) of the step."""
        from paddle_tpu.lod import row_bucket
        from paddle_tpu.profiler import runtime_metrics
        live = rows[rows > 0]
        need = int(-(-int(live.max()) // self.page_len)) if live.size else 1
        P = min(row_bucket(max(need, 1), edges=self.page_buckets),
                self.pages_per_slot)
        touched = int(np.sum(-(-live // self.page_len)))
        runtime_metrics.observe("gen.paged.pages_touched",
                                float(touched))
        runtime_metrics.observe("gen.paged.pages_in_bucket",
                                float(rows.shape[0] * P))
        if touched:
            occupancy = (100.0 * float(live.sum()) /
                         (touched * self.page_len))
            runtime_metrics.bucket("gen.paged.page_occupancy",
                                   int(occupancy))
        return P

    # -- warmup ------------------------------------------------------------
    def warmup(self):
        """AOT-compile EVERY signature an admission or a decode turn
        uses — one prefill signature per declared prompt bucket (a chunk
        bundle: per chunk rung and page bucket a chunk can run at,
        :meth:`_chunk_shapes`), one decode turn per
        declared page bucket (step, pick and state advance are one
        executable) and one seeding signature per prompt bucket
        (:func:`_seed_pool`; a chunk bundle: ``clear_slot``'s alone) —
        so the first real ``/generate`` pays zero compile time.  Returns a
        :class:`~paddle_tpu.obs.perf.WarmupReport` (int = fresh
        compiles; ``buckets`` carries one per-signature entry tagged
        ``program: prefill|decode|seed`` with compile seconds and
        cold/persistent-hit/warm provenance — what ``/stats`` surfaces
        so a rolling restart's warm claim is checkable per bucket)."""
        buckets = [b for b in self.prompt_buckets if b <= self.max_len]
        allow = False
        if self.prefill_chunks:
            # every (chunk rows, page bucket) a chunk can run at; zero
            # feeds mask every row, so the caches pass through as they
            # were.  Of the seeding signatures only ``clear_slot``'s: no
            # admission seeds
            sigs = [{k: v for k, v in {
                "gen_ids": (1, c), "gen_pos": (1, c), "gen_mask": (1, c),
                "gen_last": (1, c), "gen_slot": (1, 1),
                "gen_page_table": (1, P),
                "gen_next_ids": (1, c)}.items() if k in self._pre_feeds}
                    for c, P in self._chunk_shapes()]
            allow, buckets = self.cache_vars + self.state_vars, buckets[:1]
        else:
            sigs = [{k: v for k, v in {
                "gen_ids": (1, b), "gen_pos": (1, b), "gen_mask": (1, b),
                "gen_attn_bias": (1, 1, b, b), "gen_last": (1, b)}.items()
                if k in self._pre_feeds}
                    for b in map(self._prefill_rows, buckets)]
        from paddle_tpu.obs.perf import WarmupReport
        with self._lock:
            with self._fluid.scope_guard(self._scope):
                pre = self._exe.warmup(
                    self._pre_prog, sigs, fetch_list=self._pre_fetch,
                    scope=self._scope, allow_state_updates=allow)
                if self._body:
                    # every chunk but a prompt's last: the largest rung
                    top = self.prefill_chunks[-1]
                    pre = WarmupReport.merge(pre, self._exe.warmup(
                        self._body[0],
                        [s for s in sigs if s["gen_ids"] == (1, top)],
                        fetch_list=self._body[2], scope=self._scope,
                        allow_state_updates=allow), labels=("prefill",) * 2)
        dec = self._warm_turns()
        with self._lock:
            seed = self._warm_seeds(buckets)
        return WarmupReport.merge(pre, dec, seed,
                                  labels=("prefill", "decode", "seed"))

    def _warm_turns(self):
        """The decode turn of every declared page bucket, launched in
        both its forms: with a patch from the host (every slot's rows set
        to 0: a step over no live slot writes nothing, so the pools are
        left as they were) and with the patch that sits on the device.
        Counted as ``Executor.warmup`` counts (``warmup.signatures`` /
        ``warmup.compiles``)."""
        from paddle_tpu import profiler as _profiler
        from paddle_tpu.obs.perf import WarmupReport
        S = self.num_slots
        free, zeros = np.full(S, -1, np.int32), np.zeros(S, np.int32)

        def both_forms(pages):
            for every_row in (True, False):
                jax.block_until_ready(self._launch(
                    free, zeros, zeros, every_row, pages=pages)[1])

        with _profiler.record_latency("executor.warmup_seconds"):
            entries = [_warm_entry({"gen_page_table": [S, int(P)]},
                                   functools.partial(both_forms, P),
                                   lambda: len(self._turns))
                       for P in self.page_buckets
                       if P <= self.pages_per_slot]
        compiled = sum(e["compiles"] for e in entries)
        _profiler.runtime_metrics.inc("warmup.signatures", len(entries))
        _profiler.runtime_metrics.inc("warmup.compiles", compiled)
        return WarmupReport(compiled, entries)

    def _warm_seeds(self, buckets):
        """Run the compiled seed once per prompt bucket with a trip
        count of 0 (the pools pass through untouched); caller holds
        ``_lock``."""
        from paddle_tpu.obs.perf import WarmupReport
        idx = np.zeros(self.pages_per_slot, np.int32)
        entries = []
        for b in buckets:
            kv = self._zero_kv(b)
            entries.append(_warm_entry(
                {"kv": [len(kv)] + list(kv[0].shape)},
                lambda: self._write_pool(kv, idx, 0),
                _seed_pool._cache_size))
        return WarmupReport(sum(e["compiles"] for e in entries), entries)
