"""Program-visualization tests (``paddle_tpu.analysis.visualize``):
whole-Program DOT rendering with sub-block clusters, donation and
creation-site annotations, the typo'd ``paddle_tpu.debuger`` shim, and
the ``paddle_tpu lint --dot`` CLI exposure."""

import os
import sys
import warnings

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import visualize


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], dtype="float32",
                        append_batch_size=False)
        h = layers.fc(x, 4, act="relu")
        loss = layers.reduce_mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, ["x"], [loss.name]


class TestProgramDot:
    def test_renders_ops_vars_and_grads(self, tmp_path):
        main, feeds, fetches = _train_program()
        path = str(tmp_path / "p.dot")
        dot = visualize.program_dot(main, path=path)
        assert dot.startswith("digraph Program {")
        assert dot.rstrip().endswith("}")
        assert "mul" in dot and "_AT_GRAD" in dot
        assert "fillcolor=orange" in dot          # gradient vars
        assert os.path.exists(path)
        # every op carries its creation site as a tooltip pointing at
        # the user code that appended it (this file)
        assert 'tooltip="' in dot
        assert "test_visualize.py" in dot

    def test_donation_plan_annotations(self):
        from paddle_tpu.memory_optimization_transpiler import \
            plan_donation
        main, feeds, fetches = _train_program()
        plan = plan_donation(main, feed_names=feeds,
                             fetch_names=fetches)
        dot = visualize.program_dot(main)
        d = plan.to_dict()
        assert d["inplace_updates"], "sgd should update params in place"
        assert "[in-place @ op" in dot
        assert "peripheries=2" in dot

    def test_sub_blocks_render_as_clusters(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[1], dtype="float32",
                            append_batch_size=False)
            limit = layers.fill_constant([1], "float32", 3.0)
            cond = layers.less_than(x, limit)
            w = layers.While(cond=cond)
            with w.block():
                nxt = layers.increment(x, in_place=True)
                layers.less_than(nxt, limit, cond=cond)
        dot = visualize.program_dot(main)
        assert "subgraph cluster_b1" in dot
        assert "style=dotted" in dot    # parent-op -> sub-block edge

    def test_highlights_and_block_graph(self, tmp_path):
        main, _, fetches = _train_program()
        dot = visualize.draw_block_graphviz(
            main.global_block(), highlights=fetches, path=None)
        assert dot.startswith("digraph G {")
        assert "fillcolor=red" in dot

    def test_pprint(self):
        main, _, _ = _train_program()
        code = visualize.pprint_program_codes(main)
        assert "# block 0" in code and "mul(" in code
        fwd = visualize.pprint_block_codes(main.global_block(),
                                           show_backward=False)
        assert "_grad" not in fwd


class TestDebugerShim:
    def test_shim_warns_and_reexports(self):
        # a first touch again, whichever test file this worker ran before
        sys.modules.pop("paddle_tpu.debuger", None)
        vars(fluid).pop("debuger", None)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            from paddle_tpu import debuger
        assert any(issubclass(w.category, DeprecationWarning)
                   for w in caught)
        assert debuger.draw_block_graphviz is \
            visualize.draw_block_graphviz
        assert debuger.pprint_program_codes is \
            visualize.pprint_program_codes

    def test_package_import_does_not_warn(self):
        # the lazy __getattr__ keeps `import paddle_tpu` silent; only
        # touching the deprecated name pays the warning
        import subprocess
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=repo_root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        r = subprocess.run(
            [sys.executable, "-W", "error::DeprecationWarning",
             "-c", "import paddle_tpu"],
            cwd=repo_root, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]


class TestLintDotCLI:
    def test_lint_dot_writes_graph(self, tmp_path, capsys):
        from paddle_tpu.cli import main as cli_main
        out = str(tmp_path / "mnist.dot")
        rc = cli_main(["lint", "--zoo", "mnist", "--dot", out])
        assert rc == 0
        text = open(out).read()
        assert text.startswith("digraph Program {")
        assert "conv2d" in text

    def test_lint_dot_requires_single_main_program(self, tmp_path,
                                                   capsys):
        from paddle_tpu.cli import main as cli_main
        rc = cli_main(["lint", "--zoo", "all",
                       "--dot", str(tmp_path / "x.dot")])
        assert rc == 2
        assert "exactly one main program" in capsys.readouterr().err
