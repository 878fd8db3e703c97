"""Static FLOPs/bytes cost model (paddle_tpu/analysis/cost) and its
three consumers: bucket-edge selection, GenScheduler admission
weights, pipeline stage balancing."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import cost
from paddle_tpu.lod import row_bucket, select_bucket_edges

from fake_gen_predictor import FakeGenPredictor


def _matmul_program(m=4, k=8, n=16):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data("x", shape=[m, k], dtype="float32",
                        append_batch_size=False)
        y = layers.data("y", shape=[k, n], dtype="float32",
                        append_batch_size=False)
        out = fluid.layers.matmul(x, y)
    return main, out


class TestEstimate:
    def test_matmul_flops_exact(self):
        main, _ = _matmul_program(4, 8, 16)
        r = cost.estimate(main)
        assert r.total_flops == 2 * 4 * 8 * 16
        assert r.uncovered == []
        assert r.total_bytes > 0

    def test_report_schema_and_by_op_type(self):
        main, _ = _matmul_program()
        r = cost.estimate(main)
        assert cost.validate_cost_report(r.to_dict()) == []
        agg = r.by_op_type()
        assert agg["matmul"]["count"] == 1
        # schema negatives
        bad = r.to_dict()
        bad["total_flops"] = -1
        assert cost.validate_cost_report(bad)
        assert cost.validate_cost_report({"nope": 1})
        assert cost.validate_cost_report([])

    def test_unknown_op_lands_on_uncovered_not_guessed(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            b = main.global_block()
            b.create_var(name="x", shape=(4,), dtype="float32",
                         is_data=True)
            b.append_op("totally_unknown_op", inputs={"X": ["x"]},
                        outputs={"Out": ["o"]}, attrs={})
        r = cost.estimate(main)
        assert "totally_unknown_op" in r.uncovered
        row = next(p for p in r.per_op
                   if p["op_type"] == "totally_unknown_op")
        assert row["flops"] == 0 and row["bytes"] == 0

    def test_zoo_estimates_have_flops_and_validate(self):
        from paddle_tpu.models import build_train_program
        for name in ("mnist", "transformer"):
            main, _s, _fd, _ft = build_train_program(name)
            r = cost.estimate(main)
            assert r.total_flops > 0, name
            assert cost.validate_cost_report(r.to_dict()) == [], name

    def test_op_flops_conv_formula(self):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            img = layers.data("img", shape=[3, 8, 8], dtype="float32")
            out = fluid.layers.conv2d(img, num_filters=4,
                                      filter_size=3)
        block = main.global_block()
        conv = next(op for op in block.ops if op.type == "conv2d")
        flops = cost.op_flops(conv, block)
        o = block.var(conv.output("Output")[0])
        n, co, ho, wo = o.shape
        assert flops == 2 * max(n, 1) * ho * wo * co * 3 * 3 * 3

    def test_row_cost_fn_affine_and_monotone(self):
        main, _ = _matmul_program()
        fn = cost.row_cost_fn(main, batch_var="x", dim=0,
                              probe_rows=(4, 8))
        assert fn(8) > fn(4) > 0
        # affine: doubling rows doubles the matmul term
        assert fn(16) == pytest.approx(2 * fn(8) - fn(4) * 0,
                                       rel=0.5)


class TestSelectBucketEdges:
    def test_picks_observed_modes(self):
        # heavy mass at 7 and 32: padding everything to 32 wastes 4x
        # on the common case — the DP must cut at 7
        counts = [7] * 90 + [32] * 10
        edges = select_bucket_edges(counts, max_edges=2)
        assert edges == [7, 32]

    def test_single_edge_when_budget_is_one(self):
        edges = select_bucket_edges([3, 5, 9], max_edges=1)
        assert edges == [9]  # must cover the max

    def test_cost_weighting_changes_the_cut(self):
        # linear cost picks the big mode; a quadratic cost makes
        # padding small items to the large edge far more expensive,
        # pulling the budgeted edge toward the small mode
        counts = [4] * 10 + [5] * 10 + [16] * 2
        lin = select_bucket_edges(counts, max_edges=2)
        quad = select_bucket_edges(counts, max_edges=2,
                                   cost_of=lambda e: float(e) ** 3)
        assert lin[-1] == quad[-1] == 16
        assert set(quad) == {5, 16}

    def test_empty_and_row_bucket_integration(self):
        assert select_bucket_edges([]) == []
        edges = select_bucket_edges([3, 3, 3, 11], max_edges=2)
        assert row_bucket(2, edges) == 3
        assert row_bucket(11, edges) == 11
        # past the largest edge: pow2 ladder fallback keeps keys bounded
        assert row_bucket(17, edges) == 32


class TestGenConsumers:
    @pytest.fixture(scope="class")
    def bundle_dir(self, tmp_path_factory):
        from paddle_tpu.models import gen_lm
        d = str(tmp_path_factory.mktemp("costgen") / "bundle")
        hp = gen_lm.GenConfig()
        hp.vocab_size, hp.d_model, hp.d_ffn = 32, 16, 32
        hp.n_head = hp.n_layer = 2
        hp.d_head, hp.max_len = 16, 16
        gen_lm.export_gen_model(d, hp, num_slots=2)
        return d

    def test_prefill_cost_monotone_in_bucket(self, bundle_dir):
        from paddle_tpu.gen import GenPredictor
        p = GenPredictor(bundle_dir)
        buckets = sorted(set(p._bucket(n)
                             for n in (1, p.max_prompt_len)))
        if len(buckets) < 2:
            pytest.skip("bundle has a single prompt bucket")
        costs = [p.prefill_cost(b) for b in buckets]
        assert costs == sorted(costs)
        assert costs[0] > 0

    def test_plan_prompt_buckets(self, bundle_dir):
        from paddle_tpu.gen import GenPredictor
        p = GenPredictor(bundle_dir)
        lengths = [3] * 50 + [12] * 5
        edges = p.plan_prompt_buckets(lengths, max_edges=2)
        assert edges == [3, 12]
        assert all(e <= p.max_len for e in edges)

    def test_scheduler_prefill_budget_paces_admissions(self):
        """With a budget of one prompt's cost, each _admit pass admits
        exactly one queued request (plus the always-free first) —
        admission is paced by static cost, and the queue still
        drains."""
        from paddle_tpu.gen.scheduler import GenScheduler

        pred = FakeGenPredictor()
        s = GenScheduler(pred, queue_size=8, prefill_budget=250.0)
        try:
            streams = [s.submit([1, 2], max_new_tokens=2)
                       for _ in range(4)]
            for st in streams:
                toks = list(st)
                assert toks and toks[0] == 7
            assert st.finish_reason in ("length", "eos")
        finally:
            s.close()
        # every request was eventually prefilled despite the budget
        assert len(pred.prefill_calls) == 4

    def test_budget_is_continuous_only(self):
        """Batch admission refills the pool as one unit (the
        request-level baseline); a budget cut mid-refill would strand
        unfilled slots for a whole batch generation — so the budget is
        silently inert there."""
        from paddle_tpu.gen.scheduler import GenScheduler

        s = GenScheduler(FakeGenPredictor(), admission="batch",
                         prefill_budget=5.0)
        try:
            assert s.prefill_budget is None
        finally:
            s.close()
        s = GenScheduler(FakeGenPredictor(), prefill_budget=5.0)
        try:
            assert s.prefill_budget == 5.0
        finally:
            s.close()


class TestPipelineBalancing:
    def test_stage_weights_ride_the_shared_cost_model(self):
        from paddle_tpu.parallel.pipeline_transpiler import _op_cost
        main, _ = _matmul_program(4, 8, 16)
        block = main.global_block()
        mm = next(op for op in block.ops if op.type == "matmul")
        assert _op_cost(mm, block) == \
            1 + cost.op_flops(mm, block, default=0)
        assert _op_cost(mm, block) > 1  # really priced, not the old 1

    def test_quantile_cuts_balance_flops(self):
        # two matmuls of equal cost + cheap glue: a 2-stage split must
        # put one matmul on each side
        from paddle_tpu.parallel.pipeline_transpiler import \
            split_program
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = layers.data("x", shape=[8, 8], dtype="float32",
                            append_batch_size=False)
            w = layers.data("w", shape=[8, 8], dtype="float32",
                            append_batch_size=False)
            a = fluid.layers.matmul(x, w)
            b = fluid.layers.relu(a)
            c = fluid.layers.matmul(b, w)
            d = fluid.layers.relu(c)
        _, stage_ops, _, _ = split_program(
            main, 2, ["x", "w"], [d.name])
        types0 = [op.type for op in stage_ops[0]]
        types1 = [op.type for op in stage_ops[1]]
        assert types0.count("matmul") == 1
        assert types1.count("matmul") == 1
