"""Every serving program names its role and its sublayers
(``models/decoder.py``: ``ROLES``, ``GROUPS``; docs/observability.md "The
serving programs' scopes"), the predictor's own two executables theirs
(``gen_turn``, ``gen_seed``), and the persistent compilation cache keys
every program with its metadata, decided once.

* every op of every builder's prefill / chunk / decode program carries
  ``op_namescope`` = ``<role>/<group>`` or ``<role>/mtp/<group>``, a
  hyper-connection wrapper's own ops ``mhc`` behind that;
* a toy decode turn and a toy seeding call, lowered on the CPU: an
  instruction under a ``ptop_`` scope has ``gen_decode/<group>/`` before
  it, the turn's own instructions ``gen_turn``, the seed's ``gen_seed``;
* the key policy: the option is on whoever compiles, and nothing in
  ``paddle_tpu/`` sets it a second time."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework
from paddle_tpu.framework import unique_name_scope
from paddle_tpu.gen import GenPredictor
from paddle_tpu.gen import predictor as predictor_mod
from paddle_tpu.models import (block_moe, decoder, gen_lm, hybrid_decoder,
                               hybrid_moe, latent_moe, latent_moe_sparse,
                               latent_moe_streams, latent_moe_window,
                               window_moe)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAG = "jax_compilation_cache_include_metadata_in_key"
SLOTS, PAGE_LEN = 3, 8


class _DraftingWindow(window_moe.WindowMoEConfig):
    """The window builder with its MTP module loaded (``mtp/<group>``)."""
    num_nextn_predict_layers = 1


class _SolarLike(hybrid_moe.HybridConfig):
    """The hybrid builder's chunk form: KDA mixers, a gated softmax layer
    and shared-expert MoE sublayers."""
    pattern = "KSGS"


#: builder -> (module, toy configuration, the programs it builds)
BUILDERS = {
    "gen_lm": (gen_lm, gen_lm.GenConfig, ("prefill", "decode")),
    "hybrid_moe": (hybrid_moe, hybrid_moe.HybridConfig,
                   ("prefill", "decode")),
    "hybrid_moe_chunked": (hybrid_moe, _SolarLike, ("chunk", "decode")),
    "latent_moe": (latent_moe, latent_moe.LatentMoEConfig,
                   ("chunk", "decode")),
    "latent_moe_sparse": (latent_moe, latent_moe_sparse.SparseLatentConfig,
                          ("chunk", "decode")),
    "latent_moe_window": (latent_moe, latent_moe_window.WindowLatentConfig,
                          ("chunk", "decode")),
    "latent_moe_streams": (latent_moe,
                           latent_moe_streams.StreamsLatentConfig,
                           ("chunk", "decode")),
    "block_moe": (block_moe, block_moe.BlockMoEConfig,
                  ("prefill", "decode")),
    "window_moe": (window_moe, window_moe.WindowMoEConfig,
                   ("chunk", "decode")),
    "window_moe_drafting": (window_moe, _DraftingWindow,
                            ("chunk", "decode")),
    "hybrid_decoder": (hybrid_decoder, hybrid_decoder.HybridDecoderConfig,
                       ("chunk", "chunk_body", "chunk_all", "decode")),
}
PROGRAMS = [(name, prog) for name, (_, _, progs) in BUILDERS.items()
            for prog in progs]
ROLE_OF = {"prefill": "gen_prefill", "chunk": "gen_chunk",
           "chunk_body": "gen_chunk", "chunk_all": "gen_chunk",
           "decode": "gen_decode"}
#: the chunk program's other shapes (``hybrid_decoder``)
CROSS = {"chunk_body": None, "chunk_all": "all"}


def _build(name, prog):
    module, config, _ = BUILDERS[name]
    hp = config()
    pool = (SLOTS, PAGE_LEN, SLOTS * -(-int(hp.max_len) // PAGE_LEN))
    main = fluid.Program()
    with unique_name_scope(""), fluid.program_guard(main, fluid.Program()):
        if prog == "prefill":
            module.build_prefill_program(hp)
        elif prog == "chunk":
            module.build_chunk_program(hp, *pool)
        elif prog in CROSS:
            module.build_chunk_program(hp, *pool, cross=CROSS[prog])
        else:
            module.build_paged_decode_program(hp, *pool)
    return main


@pytest.mark.parametrize("name,prog", PROGRAMS,
                         ids=["-".join(p) for p in PROGRAMS])
def test_every_op_names_its_program_and_its_sublayer(name, prog):
    main = _build(name, prog)
    ops = [op for block in main.blocks for op in block.ops]
    assert ops
    seen = set()
    for op in ops:
        path = str(op.attrs.get(framework.OP_NAMESCOPE_ATTR, "")).split("/")
        assert path[0] == ROLE_OF[prog], (op.type, path)
        rest = path[1:]
        if rest[:1] == ["mtp"]:
            rest = rest[1:]
        if rest[1:] == ["mhc"]:
            # a wrapper's own op, inside its sublayer's group
            assert name == "latent_moe_streams", (op.type, path)
            assert op.type in ("mhc_pre", "mhc_post", "unsqueeze", "expand",
                               "reduce_sum"), (op.type, path)
            rest = rest[:1]
        assert len(rest) == 1 and rest[0] in decoder.GROUPS, (op.type, path)
        assert op.type not in ("mhc_pre", "mhc_post") \
            or path[-1] == "mhc", (op.type, path)
        seen.add("/".join(p for p in path[1:] if p != "mhc"))
    # the vocabulary is used, not merely allowed
    assert {"embed", "attn", "head"} <= seen
    assert ("mixer" in seen) == name.startswith("hybrid_")
    assert ("experts" in seen) == (name not in ("gen_lm", "hybrid_decoder"))
    drafting = name in ("window_moe_drafting", "latent_moe_streams")
    assert ("mtp/attn" in seen and "mtp/head" in seen
            and "mtp/embed" in seen) == drafting
    assert not any(s.startswith("mtp/") for s in seen) or drafting


def test_the_vocabulary_is_the_documented_one():
    """The names are API: the benchmark's needles and the documentation
    hold them (docs/observability.md)."""
    assert decoder.ROLES == ("gen_prefill", "gen_chunk", "gen_decode")
    assert decoder.GROUPS == ("embed", "attn", "mixer", "experts", "dense",
                              "head")
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    for name in decoder.ROLES + decoder.GROUPS + ("gen_turn", "gen_seed"):
        assert f"`{name}`" in doc, name
    with pytest.raises(ValueError):
        decoder.group("ffn")
    with pytest.raises(ValueError):
        decoder.program_role("gen_train")


def test_a_group_takes_the_place_of_the_group_around_it():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        with fluid.name_scope("gen_decode"), decoder.group("experts"):
            with decoder.group("dense"):
                fluid.layers.fill_constant([1], "float32", 1.0)
            with decoder.mtp_scope(), decoder.group("head"), \
                    decoder.mtp_scope(), decoder.group("head"):
                fluid.layers.fill_constant([1], "float32", 1.0)
            fluid.layers.fill_constant([1], "float32", 1.0)
    assert [op.attr(framework.OP_NAMESCOPE_ATTR)
            for op in main.global_block().ops] == [
        "gen_decode/dense", "gen_decode/experts/mtp/head",
        "gen_decode/experts"]
    assert framework.open_name_scopes() == ()


def test_a_train_program_takes_no_role():
    from paddle_tpu import models
    for name in ("gen_lm", "hybrid_moe", "latent_moe", "block_moe",
                 "window_moe"):
        with unique_name_scope(""):
            main, _, _, _ = models.build_train_program(name)
        scopes = {op.attrs.get(framework.OP_NAMESCOPE_ATTR)
                  for op in main.global_block().ops}
        assert not any(s and s.split("/")[0] in decoder.ROLES
                       for s in scopes), (name, scopes)


# ---------------------------------------------------------------------------
# the lowered executables
# ---------------------------------------------------------------------------

def _op_names(hlo_text):
    """The ``op_name`` paths of a lowered module's locations."""
    return set(re.findall(r'loc\("([^"]+)"', hlo_text))


@pytest.fixture(scope="module")
def lm(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("scopes_lm") / "bundle")
    gen_lm.export_gen_model(path, gen_lm.GenConfig(), num_slots=SLOTS)
    p = GenPredictor(path)
    p.warmup()
    return p


def test_a_decode_turn_lowers_under_its_roles(lm, monkeypatch):
    zeros = np.zeros(lm.num_slots, np.int32)
    lm.decode_step(zeros, zeros, lens=zeros)       # the device's state
    # the bare ``jax.jit`` of the turn, without the compile capture
    monkeypatch.setenv("PADDLE_TPU_PERF", "0")
    monkeypatch.setattr(lm, "_turns", {})
    turn = lm._compiled_turn(lm.page_buckets[0])
    with fluid.scope_guard(lm._scope):
        ro, inout = lm._step._record.resolve()
    text = turn.lower(lm._dev_state, lm._no_patch, ro, inout,
                      jax.random.PRNGKey(0)).as_text(debug_info=True)
    names = _op_names(text)
    stepped = [n for n in names if "ptop_" in n]
    assert stepped
    groups = "|".join(decoder.GROUPS)
    for n in stepped:
        assert re.search(rf"/pt_step/gen_decode/({groups})/ptop_", n), n
        assert "gen_turn" not in n, n
    kinds = {re.search(r"gen_decode/(\w+)/", n).group(1) for n in stepped}
    assert kinds == {"embed", "attn", "dense", "head"}
    own = [n for n in names if n.startswith("jit(turn)") and "pt_step" not in n
           and n != "jit(turn)"]
    assert own and all("/gen_turn/" in n + "/" for n in own), own


def test_the_seeding_call_lowers_under_gen_seed(lm):
    block = lm._dec_prog.global_block()
    pools = tuple(jnp.zeros(block.var(n).shape, "float32")
                  for n in lm.cache_vars)
    kv = tuple(jnp.zeros((1, 8, p.shape[-1]), "float32") for p in pools)
    idx = jnp.zeros(lm.pages_per_slot, jnp.int32)
    text = predictor_mod._seed_pool.lower(
        pools, kv, idx, np.int32(1), max_rows=8).as_text(debug_info=True)
    names = [n for n in _op_names(text) if n != "jit(_seed_pool)"
             and n.startswith("jit(")]
    assert names and all("/gen_seed/" in n + "/" for n in names), names


def test_a_prefill_lowers_under_gen_prefill(lm):
    block = lm._pre_prog.global_block()
    T = 8
    feeds = {"gen_ids": jnp.zeros((1, T), jnp.int32),
             "gen_pos": jnp.zeros((1, T), jnp.int32),
             "gen_mask": jnp.ones((1, T), jnp.float32),
             "gen_attn_bias": jnp.zeros((1, 1, T, T), jnp.float32),
             "gen_last": jnp.ones((1, T), jnp.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(lm._scope):
        parts = exe._prepare(lm._pre_prog, block, feeds,
                             tuple(v.name for v in lm._pre_fetch),
                             lm._scope)
        ro = {n: jnp.asarray(lm._scope.find_var(n))
              for n in parts["ro_names"]}
        inout = {n: jnp.asarray(lm._scope.find_var(n))
                 for n in parts["inout_names"]}
        text = jax.jit(parts["step"]).lower(
            feeds, ro, inout, jax.random.PRNGKey(0)).as_text(debug_info=True)
    stepped = [n for n in _op_names(text) if "ptop_" in n]
    assert stepped and all("/pt_step/gen_prefill/" in n for n in stepped)


# ---------------------------------------------------------------------------
# one cache-key policy
# ---------------------------------------------------------------------------

def _sees_the_flag(monkeypatch):
    """Record the option's value at every backend compile."""
    from jax._src import compiler
    seen = []
    real = compiler.compile_or_get_cached

    def spy(*args, **kwargs):
        seen.append(bool(getattr(jax.config, FLAG)))
        return real(*args, **kwargs)

    monkeypatch.setattr(compiler, "compile_or_get_cached", spy)
    return seen


def _tiny():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 3))
    return main, startup, loss


@pytest.mark.parametrize("who", ["executor", "turn", "parallel_executor"])
def test_the_flag_is_on_whoever_compiles(who, monkeypatch, tmp_path):
    assert getattr(jax.config, FLAG) is True
    seen = _sees_the_flag(monkeypatch)
    feed = {"x": np.ones((2, 4), "float32")}
    if who == "executor":
        main, startup, loss = _tiny()
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(startup, scope=scope)
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    elif who == "turn":
        gen_lm.export_gen_model(str(tmp_path), gen_lm.GenConfig(),
                                num_slots=2)
        p = GenPredictor(str(tmp_path))
        zeros = np.zeros(2, np.int32)
        p.decode_step(zeros, zeros, lens=zeros)
    else:
        main, startup, loss = _tiny()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                        main_program=main)
            pe.run(fetch_list=[loss.name], feed={"x": np.ones(
                (8, 4), "float32")})
    assert seen and all(seen), seen
    assert getattr(jax.config, FLAG) is True


def test_a_key_holds_no_call_stack():
    """jax keeps, in a function it traces once a process, the call stack
    of whoever traced it first; with the stacks in the locations a run
    that exports a bundle and a warm start lower one program to two
    modules (two cache keys).  The locations hold the names alone."""
    @jax.jit
    def draw(key):
        with jax.named_scope("gen_seed"):
            return jax.random.uniform(key, (4, 4), jnp.float32, -1.0, 1.0)

    def lowered():
        return draw.lower(jax.random.PRNGKey(1)).as_text(debug_info=True)

    jax.clear_caches()
    first_here = lowered()
    jax.clear_caches()
    jax.random.uniform(jax.random.PRNGKey(0), (4, 4), jnp.float32, -1.0,
                       1.0)           # its inner jit, traced elsewhere
    assert lowered() == first_here
    assert "gen_seed" in first_here and ".py" not in first_here


def test_nothing_in_the_package_sets_the_flag_a_second_time():
    """``grep -rn include_metadata_in_key paddle_tpu/``: one place sets
    the option, at the executor's import, and no code reads or flips it
    anywhere else."""
    hits = []
    for folder, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                for number, line in enumerate(f, 1):
                    if "include_metadata_in_key" in line:
                        hits.append((os.path.relpath(path, ROOT), number,
                                     line.strip()))
    assert len(hits) == 1, hits
    where, _, line = hits[0]
    assert where == os.path.join("paddle_tpu", "executor.py")
    assert line == f'jax.config.update("{FLAG}", True)'
    with open(os.path.join(ROOT, "paddle_tpu", "executor.py")) as f:
        source = f.read()
    at = source.index(line)
    # at module level (configuration), not inside a function or a class
    assert source[source.rfind("\n", 0, at) + 1:at] == ""
    # and the key's metadata is the names alone: no call stack
    assert jax.config.jax_traceback_in_locations_limit == 0
    first_call = source[source.index("def _first_call("):]
    first_call = first_call[:first_call.index("\ndef ")]
    assert "jax.config" not in first_call and "annotated" not in first_call
