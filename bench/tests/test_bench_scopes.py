"""Device time by stage (bench/stages.py): on synthetic events with known
answers, and on scoped traces recorded on the chip."""
import pathlib

import pytest

from bench import stages, trace

MS = 1_000_000
P = "/device:TPU:0"


def _trace(events, op_names, window=(0, 100 * MS)):
    return {"devices": {P: events}, "async": {}, "host": [],
            "window": window, "op_names": {P: op_names}}


def test_the_innermost_stage_segment_names_an_op():
    assert stages.stage_of("jit(call)/while/body/stage.encode/stage.dither/"
                           "vmap(jit(_uniform))/add") == "dither"
    assert stages.stage_of("jit(step)/stage.grad/vmap(transpose(jvp()))/"
                           "dot_general") == "grad"
    # a whole segment only
    assert stages.stage_of("jit(step)/my_stage.grad/mul") is None
    assert stages.stage_of("jit(call)/while") is None
    assert stages.stage_of("") is None


def test_the_innermost_scope_wins():
    t = _trace([("%fusion.1 = f32[2]{0} fusion(%)", 0, 10 * MS),
                ("%fusion.2 = f32[2]{0} fusion(%)", 10 * MS, 14 * MS),
                ("%copy.3 = f32[2]{0} copy(%)", 20 * MS, 21 * MS)],
               ["jit(s)/stage.encode/mul",
                "jit(s)/stage.encode/stage.dither/threefry2x32",
                "jit(s)/copy"])
    assert stages.stage_totals(t, 2) == {"encode": 5.0, "dither": 2.0,
                                         None: 0.5}
    assert stages.stage_ms(t, "encode", 2) == 5.0
    assert stages.stage_ms(t, "dither", 1) == 4.0


def test_a_loops_body_ops_count_by_self_time():
    loop = "%while.5 = f32[2]{0} while(%), body=%b"
    t = _trace([(loop, 0, 50 * MS),
                ("%fusion.1 = f32[2]{0} fusion(%)", 10 * MS, 30 * MS),
                ("%fusion.2 = f32[2]{0} fusion(%)", 30 * MS, 45 * MS),
                ("%fusion.3 = f32[2]{0} fusion(%)", 60 * MS, 70 * MS)],
               ["jit(call)/while", "jit(call)/while/body/stage.gossip/add",
                "jit(call)/while/body/stage.apply/pallas_call",
                "jit(call)/stage.apply/mul"],
               window=(0, 65 * MS))
    totals = stages.stage_totals(t, 1)
    # the loop keeps only its own 15 ms; the last op is cut by the window
    assert totals == {None: 15.0, "gossip": 20.0, "apply": 20.0}


def test_stage_time_is_averaged_over_the_chips():
    t = {"devices": {P: [("a", 0, 10 * MS)],
                     "/device:TPU:1": [("a", 0, 30 * MS)]},
         "window": (0, 100 * MS),
         "op_names": {P: ["j/stage.apply/x"],
                      "/device:TPU:1": ["j/stage.apply/x"]}}
    assert stages.stage_ms(t, "apply", 1) == 20.0


def test_a_missing_scope_reads_none():
    t = _trace([("%fusion.1 = f32[2]{0} fusion(%)", 0, 10 * MS)],
               ["jit(s)/stage.grad/mul"])
    assert stages.stage_ms(t, "gossip", 1) is None
    assert stages.read({"trace": t, "traced_steps": 1}, "gossip") is None
    assert stages.read({"trace": t, "traced_steps": 1}, "grad") == 10.0
    assert stages.read({"trace": None, "traced_steps": None}, "grad") is None


def test_instructions_are_found_in_the_compiled_text():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->f32[2]{0}}",
        "%fused_computation.1 (param_0: f32[2]) -> f32[2] {",
        '  ROOT %mul.7 = f32[2]{0} multiply(f32[2]{0} %param_0, f32[2]{0} '
        '%param_0), metadata={op_name="jit(step)/stage.grad/mul"}',
        "}",
        "ENTRY %main.9 (p.1: f32[2]) -> f32[2] {",
        '  %fusion.1 = f32[2]{0:T(256)} fusion(f32[2]{0} %p.1), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(step)/'
        'stage.grad/mul" source_file="x.py" source_line=3}',
        '  ROOT %copy.2 = (s8[2]{0}, f32[1]{0}) copy((s8[2]{0}, f32[1]{0}) '
        '%t)',
        "}"])
    insts = stages.instructions(text)
    assert insts["fusion.1"] == ("fusion", ("f32",),
                                 "jit(step)/stage.grad/mul")
    assert insts["copy.2"] == ("copy", ("s8", "f32"), "")
    # an event matches by name, opcode and result types
    assert stages.op_name_of("%fusion.1 = f32[2]{0:T(256)} fusion(%)",
                             insts) == "jit(step)/stage.grad/mul"
    assert stages.op_name_of("%fusion.1 = u32[2]{0} fusion(%)", insts) == ""
    assert stages.op_name_of("%fusion.9 = f32[2]{0} fusion(%)", insts) == ""
    t = stages.attach({"devices": {P: [
        ("%fusion.1 = f32[2]{0:T(256)} fusion(%)", 0, 5 * MS),
        ("%fusion.1 = u32[2]{0} fusion(%)", 5 * MS, 6 * MS)]},
        "window": (0, 10 * MS)}, text)
    assert t["op_names"] == {P: ["jit(step)/stage.grad/mul", ""]}
    assert stages.stage_totals(t, 1) == {"grad": 5.0, None: 1.0}


def test_op_names_survive_save_and_read(tmp_path):
    t = _trace([("a", 1, 2)], ["jit(s)/stage.apply/add"])
    stages.save(t, tmp_path / "t.json.gz")
    back = trace.read(tmp_path / "t.json.gz")
    assert back == t
    assert stages.stage_ms(back, "apply", 1) == 1e-6


@pytest.mark.parametrize("cell, want", [
    ("tiny.train", {"grad", "encode", "gossip", "apply"}),
    ("tiny.engine", {"encode", "dither", "gossip", "apply"})])
def test_a_cells_compiled_program_names_its_stages(cell, want):
    """What a reader compiles in a traced run: the cell's program as its
    system builds it, at a tiny size on the CPU."""
    from bench.tests import harness
    _, config, _, traffic, _ = harness.TINY_CELLS[cell]
    prog = stages.built(config, traffic)
    try:
        text = stages.compiled_text(prog)
    finally:
        prog.free()
    found = {stages.stage_of(n) for _, _, n in stages.instructions(
        text).values()}
    assert want <= found


RECORDED = pathlib.Path(__file__).resolve().parents[1] / "recorded"


@pytest.mark.parametrize("name", [
    "train.granite3-2b.lead2.1chip.step.json.gz",
    "engine.lead2.ring8.d16M.2steps.json.gz"])
def test_traces_recorded_before_the_scopes_read_none(name):
    t = trace.read(RECORDED / name)
    assert "op_names" not in t
    assert stages.stage_totals(t, 1) == {}
    for stage in ("grad", "encode", "dither", "gossip", "apply"):
        assert stages.read({"trace": t, "traced_steps": 1}, stage) is None


# Scoped traces recorded on a TPU v5 lite (bench/stages.py): one steady
# step of the training cell and one call of two steps of the engine cell,
# each op with the op_name of its instruction in the compiled program.
SCOPED_TRAIN = "train.granite3-2b.lead2.1chip.scoped.step.json.gz"
SCOPED_ENGINE = "engine.lead2.ring8.d16M.scoped.2steps.json.gz"


def _reader(name):
    from bench.tests import harness
    return harness.load_run(harness.ROOT).load_reader(name)


def test_recorded_training_step_by_stage():
    t = trace.read(RECORDED / SCOPED_TRAIN)
    assert stages.stage_totals(t, 1) == {
        "grad": 64.202738, "encode": 48.027005, "gossip": 2.834831,
        "apply": 45.846276, None: 14.096404}
    # the self times add up to the chip's busy time
    assert trace.busy_ns(t, P) == 175_007_254
    # at least 90% of it lies under a stage
    assert 1 - 14.096404 / 175.007254 > 0.9
    for stage, want in (("grad", 64.202738), ("encode", 48.027005),
                        ("gossip", 2.834831), ("apply", 45.846276)):
        ctx = {"trace": t, "traced_steps": 1}
        assert _reader(f"{stage}_ms.train").read(ctx) == want
    # the Pallas kernels carry their names (lead_update) into the trace
    assert trace.top_ops(t, 1)[0][0].startswith(
        "lead_update.11 tpu_custom_call(10)")


def test_recorded_engine_steps_by_stage():
    t = trace.read(RECORDED / SCOPED_ENGINE)
    assert stages.stage_totals(t, 2) == {
        "encode": 3.995782, "dither": 4.4773885, "gossip": 7.358715,
        "apply": 8.652726, None: 12.009666}
    assert trace.busy_ns(t, P) == 72_988_555
    for stage, want in (("encode", 3.995782), ("dither", 4.4773885),
                        ("gossip", 7.358715), ("apply", 8.652726)):
        ctx = {"trace": t, "traced_steps": 2}
        assert _reader(f"{stage}_ms.engine").read(ctx) == want
    # the roofline readers still find both kernels by their signature
    lu = _reader("lead_update_roofline.train").KERNEL
    enc = _reader("diff_encode_roofline.engine").KERNEL
    assert trace.kernel_ns(t, P, lu) == 17_305_452
    assert trace.kernel_ns(t, P, enc) == 7_991_564
