"""Model-zoo lint gate: the analyzer reports ZERO diagnostics across
every ``paddle_tpu/models/*`` forward+backward program (main AND
startup).  Zero false positives is part of the analyzer's contract —
a check that cries wolf on known-good programs gets turned off, and
then the next transpiler bug ships.  A new model joins the gate by
joining ``models.ZOO_MODELS`` / ``build_train_program``."""

import pytest

from paddle_tpu import analysis
from paddle_tpu.models import ZOO_MODELS, build_train_program


@pytest.mark.parametrize("name", ZOO_MODELS)
def test_zoo_model_lints_clean(name):
    main, startup, feeds, fetches = build_train_program(name)
    result = analysis.lint_program(main, feed_names=feeds,
                                   fetch_names=fetches)
    assert not result.diagnostics, (
        f"{name} forward+backward program is not lint-clean "
        f"(analyzer false positive, or a real model bug):\n"
        f"{result.format()}")
    startup_result = analysis.lint_program(startup)
    assert not startup_result.diagnostics, (
        f"{name} startup program is not lint-clean:\n"
        f"{startup_result.format()}")


@pytest.mark.parametrize("name", ZOO_MODELS)
def test_zoo_model_forward_only_lints_clean(name):
    main, _, feeds, fetches = build_train_program(name, backward=False)
    result = analysis.lint_program(main, feed_names=feeds,
                                   fetch_names=fetches)
    assert not result.diagnostics, f"{name} forward:\n{result.format()}"


def test_zoo_gate_covers_every_model_module():
    """A model module added to paddle_tpu/models without joining the
    gate would silently escape linting."""
    import os

    import paddle_tpu.models as models
    mod_dir = os.path.dirname(os.path.abspath(models.__file__))
    # decoder.py is the library the serving builders share: it builds no
    # program of its own, and every function of it is linted through the
    # five models that call it
    modules = {n[:-3] for n in os.listdir(mod_dir)
               if n.endswith(".py") and n not in ("__init__.py",
                                                   "decoder.py")}
    assert modules == set(ZOO_MODELS), (
        f"models modules {sorted(modules)} != lint-gated zoo "
        f"{sorted(ZOO_MODELS)} — add the new model to ZOO_MODELS / "
        f"build_train_program")


def test_zoo_cli_lint_exits_clean():
    """`paddle_tpu lint --zoo all` — the command CI and humans run —
    agrees with the API-level gate."""
    from paddle_tpu.cli import main
    assert main(["lint", "--zoo", "all"]) == 0


def test_gen_bundle_lints_clean(tmp_path, capsys):
    """A freshly exported generation bundle joins the zoo gate:
    `paddle_tpu lint <bundle>` lints prefill AND decode (plus the
    cross-program signature checks) as one unit, clean."""
    from paddle_tpu.cli import main
    from paddle_tpu.models import gen_lm
    hp = gen_lm.GenConfig()
    hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
    hp.n_head = hp.n_layer = 2
    hp.d_head, hp.max_len = 8, 16
    bundle = str(tmp_path / "bundle")
    gen_lm.export_gen_model(bundle, hp, num_slots=2)
    assert main(["lint", bundle]) == 0
    out = capsys.readouterr().out
    assert "3 program(s)" in out and "0 error(s)" in out
    results = analysis.lint_gen_bundle(bundle)
    assert [label for label, _ in results] == ["prefill", "decode",
                                               "bundle"]
    for label, r in results:
        assert not r.diagnostics, f"{label}:\n{r.format()}"


# ---------------------------------------------------------------------------
# typecheck coverage ratchet: the zoo-wide warn-list may shrink, never
# grow — a new model (or a rule regression) that adds uncovered op
# types must either get rules or consciously raise the ceiling here
# ---------------------------------------------------------------------------

ZOO_UNCOVERED_CEILING = 2  # exactly {while, while_grad} — ISSUE-15
# shrank 13 -> 2 by covering the LoD/array plumbing + lstm families
# (shape inference is the prerequisite for the cost model's bytes
# accounting); the two loop carriers propagate through their BODY ops'
# rules instead

#: op families frequent enough that losing their rules would blind the
#: type checker across most of the zoo (the satellite's shrink target)
MUST_BE_COVERED = {
    "mul_grad", "matmul_grad", "elementwise_add_grad", "mean_grad",
    "softmax_grad", "cross_entropy_grad", "relu_grad", "tanh_grad",
    "conv2d_grad", "pool2d_grad", "layer_norm_grad",
    "lookup_table_grad", "reshape_grad", "transpose_grad",
    "dropout_grad", "concat_grad", "reduce_sum_grad",
    "softmax_with_cross_entropy_grad", "lstm_grad",
    "sequence_pool_grad", "increment", "less_than", "sequence_pool",
    "sequence_expand", "assign_value", "max_sequence_len",
    # ISSUE-15: the families the cost model needs (bytes costing rides
    # their shape propagation) — they may never fall off again
    "lstm", "write_to_array", "read_from_array", "array_to_lod_tensor",
    "lod_tensor_to_array", "reorder_lod_tensor_by_rank",
    "lod_rank_table", "write_to_array_grad", "array_to_lod_tensor_grad",
    "lod_tensor_to_array_grad", "reorder_lod_tensor_by_rank_grad",
    # ISSUE-18: the sparse/CTR family behind the sharded-embedding
    # workload — lookup_table_grad's SelectedRows cotangent plus the
    # row-set transform ops must stay typed so the sparse optimizer
    # path and its cost pricing never go blind
    "merge_selected_rows", "get_tensor_from_selected_rows",
    "split_ids", "split_selected_rows", "nce", "nce_grad",
}


def test_zoo_uncovered_op_ratchet():
    uncovered = set()
    for name in ZOO_MODELS:
        main, _startup, feeds, fetches = build_train_program(name)
        r = analysis.lint_program(main, feed_names=feeds,
                                  fetch_names=fetches)
        uncovered.update(r.uncovered_op_types)
    blind = sorted(uncovered & MUST_BE_COVERED)
    assert not blind, (
        f"op types the type checker must keep rules for are back on "
        f"the warn-list: {blind}")
    assert len(uncovered) <= ZOO_UNCOVERED_CEILING, (
        f"zoo-wide uncovered op types grew to {len(uncovered)} "
        f"(ceiling {ZOO_UNCOVERED_CEILING}): {sorted(uncovered)} — "
        f"add @typecheck.rule coverage for the new ops instead of "
        f"raising the ceiling")


def test_selfcheck_cli_passes():
    """`paddle_tpu selfcheck` — strict zoo lint (single- and multi-
    program) plus every scanner-enforced registry in one exit-coded
    pass; drift in any section fails tier-1 here."""
    from paddle_tpu.cli import main
    assert main(["selfcheck"]) == 0
