"""``profiler.compiled_op_table`` on a hand-made device trace: leaf events
only (a parent and the two it encloses count once), the mean over device
planes, grouping by role / name scope / op type, the ``.remat`` overlay
and the ``(unscoped)`` row, rows summing to the busy union."""

import pytest

from paddle_tpu import profiler

# the protobuf module the reduction itself reads the trace with
xplane_pb2 = profiler._xplane_pb2()

FFN = "jit(multi)/jit(main)/while/body/pt_step/enc0/ffn/ptop_mul__a/dot"
FFN_BWD = ("jit(multi)/jit(main)/while/body/pt_step/bwd/enc0/ffn/"
           "ptop_mul_grad__b/transpose(jvp(dot))")
ADAM = "jit(multi)/jit(main)/while/body/pt_step/opt/ptop_adam__w/mul"

# (name, scope, start_ns, end_ns): a while encloses two fusions; a copy
# and the optimizer's fusion follow it
OPS = [("%while.1 = (...) while(...)", "", 0, 100),
       ("%fusion.1 = bf16[8]{0} fusion(...)", FFN, 10, 40),
       ("%fusion.2.remat = bf16[8]{0} fusion(...)", FFN_BWD, 50, 90),
       ("%copy.3 = bf16[8]{0} copy(...)", "", 110, 120),
       ("%fusion.4 = f32[8]{0} fusion(...)", ADAM, 120, 150)]


def _plane(space, name, scale):
    plane = space.planes.add()
    plane.name = name
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    line = plane.lines.add()
    line.name = "XLA Ops"
    line.timestamp_ns = 1000
    for i, (op, scope, start, end) in enumerate(OPS, start=1):
        meta = plane.event_metadata[i]
        meta.id, meta.name = i, op
        if scope:
            stat = meta.stats.add()
            stat.metadata_id, stat.str_value = 1, scope
        ev = line.events.add()
        ev.metadata_id = i
        ev.offset_ps = start * 1000 * scale
        ev.duration_ps = (end - start) * 1000 * scale
    # a line that is no op timeline: one event over the whole run
    modules = plane.lines.add()
    modules.name = "XLA Modules"
    plane.event_metadata[99].id = 99
    plane.event_metadata[99].name = "jit_multi"
    ev = modules.events.add()
    ev.metadata_id, ev.duration_ps = 99, 150 * 1000 * scale


@pytest.fixture
def trace_dir(tmp_path):
    space = xplane_pb2.XSpace()
    _plane(space, "/device:TPU:0", 1)
    _plane(space, "/device:TPU:1", 3)       # a chip three times slower
    host = space.planes.add()
    host.name = "/host:CPU"
    (tmp_path / "t.xplane.pb").write_bytes(space.SerializeToString())
    return str(tmp_path)


NS = 1e-9


def test_leaves_once_mean_over_planes_rows_sum_to_busy(trace_dir):
    groups = profiler.compiled_op_groups(
        trace_dir, by=("role", "scope", "type"), depth=2)
    rows = {r[:3]: r[3:] for r in groups["rows"]}
    # plane 0 and plane 1 (x3) averaged: x2
    assert rows[("fwd", "enc0/ffn", "mul")] == (1, pytest.approx(60 * NS))
    assert rows[("bwd", "enc0/ffn", "mul_grad")] == \
        (1, pytest.approx(80 * NS))
    assert rows[("opt", "-", "adam")] == (1, pytest.approx(60 * NS))
    assert rows[(profiler.UNSCOPED, "", "")] == (1, pytest.approx(20 * NS))
    assert len(rows) == 4, "the enclosing while is no row"
    assert groups["planes"] == 2
    assert groups["remat_seconds"] == pytest.approx(80 * NS)
    # union per plane: [0,100] + [110,150] = 140; the leaves leave the
    # while's own 30 ns (glue between its children) out
    assert groups["busy_seconds"] == pytest.approx(280 * NS)
    assert sum(r[-1] for r in groups["rows"]) == pytest.approx(220 * NS)


def test_depth_and_by(trace_dir):
    by_role = profiler.compiled_op_groups(trace_dir, by=("role",))
    assert {r[0]: r[-1] for r in by_role["rows"]} == {
        "fwd": pytest.approx(60 * NS), "bwd": pytest.approx(80 * NS),
        "opt": pytest.approx(60 * NS),
        profiler.UNSCOPED: pytest.approx(20 * NS)}
    shallow = profiler.compiled_op_groups(trace_dir, by=("scope",), depth=1)
    assert {r[0] for r in shallow["rows"]} == \
        {"enc0", "-", profiler.UNSCOPED}
    with pytest.raises(ValueError):
        profiler.compiled_op_groups(trace_dir, by=("layer",))


def test_default_by_keeps_the_old_row_shape(trace_dir):
    table, rows = profiler.compiled_op_table(trace_dir)
    assert table.startswith("Event")
    assert [r[0] for r in rows] == ["mul_grad", "mul", "adam",
                                    profiler.UNSCOPED] or \
        [r[0] for r in rows] == ["mul_grad", "adam", "mul",
                                 profiler.UNSCOPED]
    for op_type, calls, seconds in rows:
        assert isinstance(op_type, str) and calls == 1 and seconds > 0


def test_grouped_table_text(trace_dir):
    table, rows = profiler.compiled_op_table(
        trace_dir, by=("role", "scope", "type"))
    lines = table.splitlines()
    assert lines[1].startswith("bwd enc0/ffn mul_grad")
    assert any(line.startswith(".remat") for line in lines)
    assert any(line.startswith(profiler.UNSCOPED) for line in lines)
    assert lines[-1].startswith("busy (union, mean of 2 chips)")
    by_calls, _ = profiler.compiled_op_table(trace_dir, "calls")
    assert by_calls.startswith("Event")
