"""Model zoo: program-builder functions for the benchmark workloads the
reference ships under ``benchmark/fluid/`` (mnist, resnet, vgg,
machine_translation/transformer, stacked_dynamic_lstm) — re-built on the
TPU-native layers API."""

from paddle_tpu.models import (resnet, transformer, vgg, mnist,
                               seq2seq, stacked_lstm, decoder, gen_lm,
                               gen_lm_long, wide_and_deep, hybrid_moe,
                               latent_moe, latent_moe_sparse,
                               latent_moe_window, latent_moe_streams,
                               block_moe, window_moe, hybrid_decoder)

__all__ = ["resnet", "transformer", "vgg", "mnist",
           "seq2seq", "stacked_lstm", "decoder", "gen_lm", "gen_lm_long",
           "wide_and_deep", "hybrid_moe", "latent_moe",
           "latent_moe_sparse", "latent_moe_window", "latent_moe_streams",
           "block_moe",
           "window_moe", "hybrid_decoder", "ZOO_MODELS",
           "build_train_program", "synth_feed", "compile_zoo_step"]

#: zoo model names accepted by :func:`build_train_program` (and by
#: ``paddle_tpu lint --zoo``; the lint gate in
#: tests/test_analysis_zoo.py iterates exactly this list)
ZOO_MODELS = ("mnist", "resnet", "vgg", "transformer", "seq2seq",
              "stacked_lstm", "gen_lm", "gen_lm_long", "wide_and_deep",
              "hybrid_moe", "latent_moe", "latent_moe_sparse",
              "latent_moe_window", "latent_moe_streams", "block_moe",
              "window_moe", "hybrid_decoder")


#: the serving decoders' entries: name -> (configuration class, its
#: teacher-forced ``(seq_len, hp)`` train program over 16 rows)
_DECODERS = {
    # one layer of each kind (mixer, attention, experts)
    "hybrid_moe": (hybrid_moe.HybridConfig,
                   hybrid_moe.hybrid_moe_train_program),
    # a dense and two expert layers
    "latent_moe": (latent_moe.LatentMoEConfig,
                   latent_moe.latent_moe_train_program),
    # the same under learned sparse attention: 16 rows of which a row
    # attends 4, chosen by the first layer's indexer
    "latent_moe_sparse": (latent_moe_sparse.SparseLatentConfig,
                          latent_moe_sparse.latent_moe_sparse_train_program),
    # a full layer under its own indexer (4 of 16 rows) and two window
    # layers of latent attention (window 8), gated head-wise
    "latent_moe_window": (
        latent_moe_window.WindowLatentConfig,
        latent_moe_window.latent_moe_window_train_program),
    # four residual streams, a wrapper a sublayer (hyper-connections)
    "latent_moe_streams": (
        latent_moe_streams.StreamsLatentConfig,
        latent_moe_streams.latent_moe_streams_train_program),
    # two layers under the block-causal mask
    "block_moe": (block_moe.BlockMoEConfig,
                  block_moe.block_moe_train_program),
    # a dense full layer and two expert window layers (window 8 of 16
    # rows, a sink a head)
    "window_moe": (window_moe.WindowMoEConfig,
                   window_moe.window_moe_train_program),
    # two Mamba-1 layers, a window layer (window 8 of 16 rows), the full
    # layer, a memory unit and a cross layer
    "hybrid_decoder": (hybrid_decoder.HybridDecoderConfig,
                       hybrid_decoder.hybrid_decoder_train_program),
}


def build_train_program(name, backward=True):
    """Build one zoo model's forward(+backward+optimizer) program with
    small smoke-test dimensions.

    Returns ``(main_program, startup_program, feed_names, fetch_names)``
    — ``feed_names`` is None when the model builds its own feed vars
    (the analyzer then infers them from ``is_data``).  Shared by
    ``paddle_tpu lint --zoo`` and the model-zoo lint gate so the CLI and
    CI analyze the same programs.
    """
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if name == "mnist":
            cost, acc, feeds = mnist.mnist_train_program(8)
            fetches = [cost.name, acc.name]
        elif name == "resnet":
            cost, acc, feeds = resnet.resnet_train_program(
                2, class_dim=10, depth=18, image_shape=(3, 32, 32))
            fetches = [cost.name, acc.name]
        elif name == "vgg":
            cost, acc, feeds = vgg.vgg_train_program(2, class_dim=10)
            fetches = [cost.name, acc.name]
        elif name == "transformer":
            hp = transformer.ModelHyperParams()
            hp.d_model, hp.d_inner_hid, hp.n_layer, hp.n_head = 32, 64, 1, 2
            hp.d_key = hp.d_value = 16
            hp.src_vocab_size = hp.trg_vocab_size = 64
            hp.max_length = 16
            cost, _ = transformer.transformer(2, 8, 8, hp)
            feeds, fetches = None, [cost.name]
        elif name == "seq2seq":
            cost, _ = seq2seq.seq_to_seq_net(
                16, 16, emb_dim=8, encoder_size=8, decoder_size=8)
            feeds, fetches = None, [cost.name]
        elif name == "stacked_lstm":
            cost, acc, _ = stacked_lstm.stacked_lstm_net(
                dict_size=16, emb_dim=8, hidden_dim=8, n_layers=2)
            feeds, fetches = None, [cost.name, acc.name]
        elif name == "gen_lm":
            hp = gen_lm.GenConfig()
            hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
            hp.n_head = hp.n_layer = 2
            hp.d_head, hp.max_len = 8, 16
            cost, feeds = gen_lm.gen_lm_train_program(2, 8, hp)
            fetches = [cost.name]
        elif name == "wide_and_deep":
            cost, acc, feeds = wide_and_deep.wide_and_deep_train_program(
                4, vocab_size=16, num_slots=2, emb_dim=4, dense_dim=4,
                hidden=8)
            fetches = [cost.name, acc.name]
        elif name == "gen_lm_long":
            # flagship long-context geometry: max_len stays at the
            # GenLongConfig 256 (the gated axis); the rest shrinks to
            # smoke-test scale like the base gen_lm entry
            hp = gen_lm_long.GenLongConfig()
            hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
            hp.n_head = hp.n_layer = 2
            hp.d_head = 8
            cost, feeds = gen_lm_long.gen_lm_long_train_program(2, 16, hp)
            fetches = [cost.name]
        elif name in _DECODERS:
            # at the configuration's toy widths, float32 (training keeps
            # float32 parameters; the serving bundle's are bfloat16)
            config, train_program = _DECODERS[name]
            hp = config()
            hp.dtype = "float32"
            cost, feeds = train_program(16, hp)
            fetches = [cost.name]
        else:
            raise ValueError(
                f"unknown zoo model {name!r}; expected one of "
                f"{ZOO_MODELS}")
        if backward:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
    return main, startup, feeds, fetches


def synth_feed(main_program, feed_names=None, batch=2):
    """Synthetic (zero-filled) feed dict for a zoo main program — what
    ``paddle_tpu profile compile|memory`` and the selfcheck ``perf``
    section execute one step with to force a real compile without a
    dataset.  Zeros are valid everywhere the zoo reads labels or token
    ids (class/token 0 exists); dynamic dims synthesize as ``batch``.
    ``feed_names=None`` falls back to the program's ``is_data`` vars
    (models that build their own feed layers)."""
    block = main_program.global_block()
    if feed_names is None:
        feed_names = [v.name for v in block.vars.values()
                      if getattr(v, "is_data", False)]
    from paddle_tpu.io import synth_feed_value

    feed = {}
    for name in feed_names:
        var = block.var(name)
        shape = tuple(batch if d is None or int(d) < 0 else int(d)
                      for d in (var.shape or (batch,)))
        feed[name] = synth_feed_value(shape, var.dtype or "float32")
    return feed


def compile_zoo_step(name, batch=2):
    """Fresh-compile one zoo model: build, run startup, run ONE
    synthetic train step in a fresh scope — the shared recipe
    ``paddle_tpu profile compile|memory`` and selfcheck's ``perf``
    section use to force a real captured compile without a dataset.
    Returns the scope (for a following HBM census)."""
    import paddle_tpu as fluid

    main, startup, feeds, fetches = build_train_program(name)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=synth_feed(main, feeds, batch=batch),
                fetch_list=fetches, scope=scope)
    return scope
