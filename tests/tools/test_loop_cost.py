"""``tools/loop_cost.py``: a compiled loop's cost read branch by branch
(ISSUE 43). A text written by hand holds every shape the reader has to
take (a fusion's cycles, a ``call``, a conditional in both spellings,
one nested in a branch, a tile loop round the round's loop, the stack
frame tables); one real text, compiled for a described TPU where this
installation can describe one, holds it to the compiler's own print."""

import importlib.util
import json
import os

import pytest

TOOL = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                    "loop_cost.py")


@pytest.fixture(scope="module")
def loop_cost():
    spec = importlib.util.spec_from_file_location("loop_cost", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def op(name, kind, cycles=None, frame=None, scope="raft_deliver", more=""):
    cfg = ("" if cycles is None else
           ', backend_config={"window_config":{"estimated_cycles":"%d"}}'
           % cycles)
    meta = f'metadata={{op_name="jit(loop)/while/body/{scope}/{kind}"' + (
        "" if frame is None else f" stack_frame_id={frame}") + "}"
    return (f"  %{name} = s32[1024,32]{{0,1:T(8,128)}} {kind}(%p){more}, "
            f"{meta}{cfg}")


TEXT = "\n".join([
    "HloModule jit_loop, is_scheduled=true",
    "",
    "FileNames",
    '1 "/root/repo/etcd_tpu/batched/step.py"',
    "",
    "FunctionNames",
    '1 "_deliver_vectorized"',
    '2 "_deliver_vectorized.<locals>.heartbeats"',
    "",
    "FileLocations",
    "1 {file_name_id=1 function_name_id=1 line=1272 end_line=1272 column=4 "
    "end_column=9}",
    "2 {file_name_id=1 function_name_id=2 line=1224 end_line=1226 column=8 "
    "end_column=9}",
    "",
    "StackFrames",
    "1 {file_location_id=1 parent_frame_id=1}",
    "2 {file_location_id=2 parent_frame_id=2}",
    "",
    "%fused.1 (p: s32[1024,32]) -> s32[1024,32] {",
    op("inside", "add", 999_999),  # a fusion's cycles are on the fusion
    "}",
    "",
    "%callee (p: s32[1024]) -> s32[1024] {",
    op("c1", "fusion", 40, more=", kind=kLoop, calls=%fused.1"),
    "}",
    "",
    "%skip (p: s32[1024]) -> s32[1024] {",
    op("s1", "copy", 7),
    "}",
    "",
    "%inner_a (p: s32[1024]) -> s32[1024] {",
    op("ia", "fusion", 100, more=", kind=kLoop, calls=%fused.1"),
    "}",
    "",
    "%inner_b (p: s32[1024]) -> s32[1024] {",
    op("ib", "fusion", 300, more=", kind=kLoop, calls=%fused.1"),
    "}",
    "",
    "%taken (p: s32[1024]) -> s32[1024] {",
    op("t1", "fusion", 1000, more=", kind=kLoop, calls=%fused.1"),
    op("t2", "call", more=", to_apply=%callee"),
    op("nested", "conditional",
       more=", branch_computations={%inner_a, %inner_b}"),
    "}",
    "",
    "%then (p: s32[1024]) -> s32[1024] {",
    op("th", "fusion", 500, more=", kind=kLoop, calls=%fused.1"),
    "}",
    "",
    "%else (p: s32[1024]) -> s32[1024] {",
    "}",
    "",
    "%round (p: s32[1024]) -> s32[1024] {",
    op("f1", "fusion", 10_000, scope="raft_tick",
       more=", kind=kLoop, calls=%fused.1"),
    "  %ring.2 = s32[1024,32]{1,0:T(8,128)} copy(%p), "
    'backend_config={"window_config":{"estimated_cycles":"5"}}',
    op("cond.1", "conditional", frame=2,
       more=", branch_computations={%skip, %taken}"),
    op("cond.2", "conditional", scope="raft_route",
       more=", true_computation=%then, false_computation=%else"),
    op("f2", "call", more=", to_apply=%callee"),
    "}",
    "",
    "%tile (p: s32[1024]) -> s32[1024] {",
    op("slice", "fusion", 77, scope="raft_tiles",
       more=", kind=kLoop, calls=%fused.1"),
    op("while.1", "while", more=", condition=%cond_fn, body=%round"),
    "}",
    "",
    "ENTRY %main (p: s32[1024]) -> s32[1024] {",
    op("while.2", "while", more=", condition=%cond_fn, body=%tile"),
    "}",
    "",
])


def test_it_reads_the_rounds_loop_branch_by_branch(loop_cost):
    loop = loop_cost.read(TEXT)
    assert loop.body == "round"  # the body with the conditionals, not the tiles'
    assert loop.flat == 10_000 + 5 + 40
    first, second = loop.conds
    assert first.name == "cond.1"
    # Not taken; taken with its call and the dearer branch of its own cond.
    assert first.branches == [7, 1000 + 40 + 300]
    assert first.where == "heartbeats:1224 < _deliver_vectorized:1272"
    assert "raft_deliver" in first.op_name
    # The predicated spelling, read as indexes: 0 is the branch not taken.
    assert second.branches == [0, 500] and second.where == ""
    # Every `,32]{0,1` but one stands N-minor.
    assert loop.ring_ring_minor == 1
    assert loop.ring_n_minor == TEXT.count(",32]{0,1") > 10


def test_a_scenario_sums_one_branch_of_each(loop_cost):
    loop = loop_cost.read(TEXT)
    flat = loop.flat
    assert loop_cost.scenario(loop, "") == flat + 1340 + 500
    assert loop_cost.scenario(loop, "0,1") == flat + 7 + 500
    assert loop_cost.scenario(loop, "1,0") == flat + 1340
    assert loop_cost.scenario(loop, "0") == flat + 7 + 500  # the rest dearest
    assert loop_cost.scenario(loop, "-,0") == flat + 1340
    with pytest.raises(ValueError, match="3 branches named for 2"):
        loop_cost.scenario(loop, "0,0,0")
    # What the all-branches sum would have said of the same loop: the
    # branch a split adds counts as a cost.
    assert sum(sum(c.branches) for c in loop.conds) + flat > (
        loop_cost.scenario(loop, ""))


def test_the_command_prints_the_table_and_the_json(loop_cost, tmp_path, capsys):
    path = tmp_path / "loop.txt"
    path.write_text(TEXT)
    assert loop_cost.main([str(path), "--take", "0,1"]) == 0
    out = capsys.readouterr().out
    assert "2 conditionals" in out and "ring-minor 1" in out
    assert "7 | 1,340" in out and "heartbeats:1224" in out
    assert "flat + the branches taken (0,1): 10,552" in out
    assert loop_cost.main([str(path), "--take", "0,1", "--json"]) == 0
    got = json.loads(capsys.readouterr().out)
    assert got["taken"] == 10_552 and got["dearest"] == 11_885
    assert [c["branches"] for c in got["conds"]] == [[7, 1340], [0, 500]]


def test_a_text_with_no_loop_is_refused(loop_cost):
    with pytest.raises(ValueError, match="no while loop"):
        loop_cost.read("HloModule m\n\nENTRY %main (p: s32[]) -> s32[] {\n}\n")


def test_it_reads_what_the_tpu_compiler_prints(loop_cost):
    """A real text: ``test_ring_layout``'s lane cond, the ring led
    through it, compiled for a described v5e (about two seconds)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from ..batched.test_ring_layout import compiled_lane_cond, rings

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = compiled_lane_cond(True, SingleDeviceSharding(topo.devices[0]))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    loop = loop_cost.read(text)
    assert (loop.ring_n_minor, loop.ring_ring_minor) == rings(text)
    (cond,) = loop.conds
    skipped, taken = cond.branches
    assert taken > skipped >= 0 and loop.flat > 0
    assert "compiled_lane_cond" in cond.where or "body" in cond.where
    assert loop_cost.scenario(loop, "1") == loop.flat + taken
