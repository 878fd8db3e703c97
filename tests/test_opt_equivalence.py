"""Zoo-wide golden-equivalence harness (ISSUE-15 acceptance): every
optimized program's fetches match the unoptimized program's on
synthetic feeds — forward, forward+backward+optimizer, and the gen
prefill/decode bundle.  RNG-bearing programs (dropout) must match
EXACTLY: the passes' ``__rng_slots__`` bookkeeping keeps every
surviving op's fold_in key at its unoptimized position."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis.opt import optimize_program
from paddle_tpu.models import ZOO_MODELS, build_train_program


def _id_bound(block, name):
    """One past the largest valid value of the integer feed ``name``:
    the class count of the cross-entropy that takes it as its label or
    the height of the table it indexes, followed through reshapes; the
    smallest where it has several consumers (ops come in program
    order, so one pass follows it)."""
    aliases, bounds = {name}, []
    for op in block.ops:
        for slot, args in op.inputs.items():
            if aliases.isdisjoint(args):
                continue
            if op.type == "reshape":
                aliases.update(op.output_arg_names)
            elif op.type == "lookup_table" and slot == "Ids":
                bounds.append(block.var(op.input("W")[0]).shape[0])
            elif slot == "Label" and op.type in (
                    "cross_entropy", "softmax_with_cross_entropy"):
                scores = op.input("X") or op.input("Logits")
                bounds.append(block.var(scores[0]).shape[-1])
    if not bounds:
        raise ValueError(f"integer feed {name!r}: no consumer says which "
                         f"values are valid")
    return int(min(bounds))


def golden_feed(name, main_program, feed_names, seed=7):
    """A deterministic, VALID feed per zoo model (zero feeds make the
    transformer loss nan through its zero-token normalizer; LoD models
    need real row-splits)."""
    from paddle_tpu.models import seq2seq, stacked_lstm, transformer
    if name == "transformer":
        hp = transformer.ModelHyperParams()
        hp.src_vocab_size = hp.trg_vocab_size = 64
        return transformer.fake_batch(2, 8, 8, hp, seed=seed)
    if name == "seq2seq":
        return seq2seq.fake_batch(4, 5, 5, 16, 16, seed=seed)
    if name == "stacked_lstm":
        return stacked_lstm.fake_batch(4, 6, 16, seed=seed)
    # dense models: random values in valid ranges (labels inside their
    # head's class count, ids inside their table)
    rng = np.random.RandomState(seed)
    block = main_program.global_block()
    if feed_names is None:
        feed_names = [v.name for v in block.vars.values()
                      if getattr(v, "is_data", False)]
    feed = {}
    for fname in feed_names:
        var = block.var(fname)
        shape = tuple(2 if d is None or int(d) < 0 else int(d)
                      for d in (var.shape or (2,)))
        if var.dtype in ("int32", "int64"):
            feed[fname] = rng.randint(
                0, _id_bound(block, fname), size=shape).astype(
                var.dtype if var.dtype == "int32" else "int64")
        else:
            feed[fname] = rng.standard_normal(shape).astype("float32")
    return feed


def _run_pair(name, backward):
    main, startup, feeds, fetches = build_train_program(
        name, backward=backward)
    main.random_seed = startup.random_seed = 11
    optimized, report = optimize_program(main, feed_names=feeds,
                                         fetch_names=fetches)
    assert not report.aborted_passes, (
        f"{name}: sandwich-aborted passes {report.aborted_passes}")
    feed = golden_feed(name, main, feeds)
    outs = []
    for prog in (main, optimized):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            outs.append(exe.run(prog, feed=feed, fetch_list=fetches,
                                scope=scope))
    return fetches, outs[0], outs[1]


@pytest.mark.parametrize("name", ZOO_MODELS)
def test_train_step_fetches_match(name):
    fetches, ref, opt = _run_pair(name, backward=True)
    for fname, a, b in zip(fetches, ref, opt):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), \
            f"{name}: reference fetch {fname!r} is not finite"
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6,
            err_msg=f"{name}: fetch {fname!r} diverged under "
                    f"optimization (fwd+bwd+optimizer)")


@pytest.mark.parametrize("name", ("mnist", "transformer", "gen_lm"))
def test_forward_only_fetches_match(name):
    fetches, ref, opt = _run_pair(name, backward=False)
    for fname, a, b in zip(fetches, ref, opt):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
            err_msg=f"{name}: fetch {fname!r} diverged (forward)")


class TestGenBundleEquivalence:
    """The gen prefill/decode bundle under PADDLE_TPU_OPT=1: greedy
    tokens from a fresh optimized predictor must equal the unoptimized
    predictor's, token for token."""

    @pytest.fixture(scope="class")
    def bundle_dir(self, tmp_path_factory):
        from paddle_tpu.models import gen_lm
        d = str(tmp_path_factory.mktemp("optgen") / "bundle")
        hp = gen_lm.GenConfig()
        hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
        hp.n_head = hp.n_layer = 2
        hp.d_head, hp.max_len = 8, 16
        gen_lm.export_gen_model(d, hp, num_slots=2)
        return d

    def _greedy(self, bundle_dir, prompt, n=6):
        from paddle_tpu.gen import GenPredictor
        p = GenPredictor(bundle_dir)
        logits, kv = p.prefill(prompt)
        toks = [int(np.argmax(logits))]
        p.alloc_slot_pages(0, p.pages_needed(len(prompt), n))
        p.write_slot(0, kv, len(prompt))
        pos = len(prompt)
        last = toks[0]
        S = p.num_slots
        for _ in range(n - 1):
            tokens = np.zeros(S, np.int32)
            positions = np.zeros(S, np.int32)
            lens = np.zeros(S, np.int32)
            tokens[0] = last
            positions[0] = pos
            lens[0] = pos + 1
            step = p.decode_step(tokens, positions, lens=lens)
            last = int(np.argmax(step[0]))
            toks.append(last)
            pos += 1
        return toks

    def test_greedy_tokens_identical(self, bundle_dir, monkeypatch):
        prompt = [3, 1, 4, 1, 5]
        monkeypatch.delenv("PADDLE_TPU_OPT", raising=False)
        ref = self._greedy(bundle_dir, prompt)
        monkeypatch.setenv("PADDLE_TPU_OPT", "1")
        opt = self._greedy(bundle_dir, prompt)
        assert ref == opt, (
            f"gen bundle decode diverged under optimization: "
            f"{ref} vs {opt}")
