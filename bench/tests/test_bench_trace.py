"""The reduction from trace events to per-layer numbers, on synthetic events
with known answers."""
import json
import pathlib

import pytest

from bench import trace

MS = 1_000_000


def _trace(devices, window=(0, 100 * MS), host=()):
    return {"devices": devices, "window": window, "host": list(host)}


def test_union_length_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert trace.length([(0, 10), (5, 15), (20, 25)]) == 20
    assert trace.subtract([(0, 10)], [(2, 4), (6, 7)]) == [(0, 2), (4, 6),
                                                           (7, 10)]
    assert trace.subtract([(0, 10)], [(0, 10)]) == []
    assert trace.subtract([(0, 10)], []) == [(0, 10)]


def test_busy_counts_overlapping_ops_once_and_clips_to_the_window():
    t = _trace({"/device:TPU:0": [("fusion.1", 10 * MS, 40 * MS),
                                  ("fusion.2", 30 * MS, 50 * MS),
                                  ("copy.3", 90 * MS, 130 * MS)]})
    assert trace.busy_ns(t, "/device:TPU:0") == 50 * MS
    assert trace.idle_share(t) == pytest.approx(0.5)


def test_idle_share_is_the_largest_over_the_chips():
    t = _trace({"/device:TPU:0": [("a", 0, 90 * MS)],
                "/device:TPU:1": [("a", 0, 60 * MS)]})
    assert trace.idle_share(t) == pytest.approx(0.4)


LEAD = {"name": "lead_update", "target": "tpu_custom_call", "operands": 10,
        "results": ("f32",) * 4}
ENCODE = {"name": "lead_diff_encode", "target": "tpu_custom_call",
          "operands": 6, "results": ("s8", "f32")}
F32 = "f32[256,512]{1,0:T(8,128)}"


def _call(inst, results, operands, target="tpu_custom_call"):
    name = (f"%{inst} = ({', '.join(results)}) custom-call("
            + ", ".join(f"f32[1,1]{{1,0}} %a.{i}" for i in range(operands))
            + f'), custom_call_target="{target}", operand_layout_'
            'constraints={f32[1,1]{1,0}}')
    return name


def test_hlo_text_parts():
    h = trace.hlo(_call("step.23", [F32] * 4, 10))
    assert (h["inst"], h["opcode"], h["target"], h["operands"]) == (
        "step.23", "custom-call", "tpu_custom_call", 10)
    assert h["results"] == ("f32",) * 4
    h = trace.hlo("%fusion.3 = bf16[2,1024]{1,0:T(8,128)(2,1)} fusion("
                  "f32[2,1024]{1,0} %p.1, s32[] %p.2), kind=kLoop")
    assert (h["inst"], h["opcode"], h["operands"], h["results"]) == (
        "fusion.3", "fusion", 2, ("bf16",))
    assert trace.hlo(trace.compact(_call("x.1", [F32] * 4, 10))) == \
        trace.hlo(_call("x.1", [F32] * 4, 10))


def test_kernels_are_known_by_their_call_signature():
    ops = [(_call("step.1", [F32] * 4, 10), 0, 2 * MS),
           (_call("closed_call.7", [F32] * 4, 10), 5 * MS, 8 * MS),
           (_call("closed_call.8", ["s8[256,512]{1,0}", "f32[256,1]{1,0}"],
                  6), 10 * MS, 11 * MS),
           (_call("closed_call.9", [F32] * 4, 9), 12 * MS, 13 * MS),
           (_call("c.2", [F32] * 4, 10, target="Sharding"), 14 * MS,
            15 * MS),
           ("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
            20 * MS, 21 * MS)]
    t = _trace({"/device:TPU:0": ops})
    assert trace.kernel_ns(t, "/device:TPU:0", LEAD) == 5 * MS
    assert trace.kernel_ns(t, "/device:TPU:0", ENCODE) == 1 * MS


def test_collective_time_and_its_exposed_part():
    ops = [("fusion.1", 0, 40 * MS),
           ("collective-permute-start.2", 30 * MS, 60 * MS),
           ("fusion.4", 50 * MS, 55 * MS),
           ("collective-permute-done.3", 70 * MS, 72 * MS)]
    t = _trace({"/device:TPU:0": ops})
    p = "/device:TPU:0"
    assert trace.collective_ns(t, p) == 32 * MS
    # 40-50 and 55-60 of the first collective, and all of the second
    assert trace.exposed_collective_ns(t, p) == 17 * MS


def test_top_ops_count_self_time_and_idle_gaps_name_what_ran():
    host = [("window", 0, 100 * MS), ("step", 0, 50 * MS),
            ("batch", 40 * MS, 50 * MS)]
    loop = "%while.5 = f32[2]{0} while(f32[2]{0} %t), body=%b"
    fus = "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
    copy = "%copy.2 = f32[2]{0} copy(f32[2]{0} %p)"
    t = _trace({"/device:TPU:0": [(loop, 0, 40 * MS), (fus, 10 * MS, 30 * MS),
                                  (fus, 50 * MS, 70 * MS),
                                  (copy, 70 * MS, 100 * MS)]},
               host=host)
    assert trace.top_ops(t) == [["fusion.1 fusion", 0.04],
                                ["copy.2 copy", 0.03],
                                ["while.5 while", 0.02]]
    assert trace.idle_gaps(t) == [["batch", 0.01]]


def test_save_and_read_round_trip(tmp_path):
    t = _trace({"/device:TPU:0": [("a", 1, 2)]},
               host=[("window", 0, 100 * MS)])
    t["async"] = {"/device:TPU:0": [("b", 3, 4)]}
    trace.save(t, tmp_path / "t.json.gz")
    assert trace.read(tmp_path / "t.json.gz") == t


# Traces recorded on a TPU v5 lite and trimmed (bench/recorded/): one
# steady step of the 1-chip training cell as first configured, with its
# head untied (12 parameter leaves), and two steps of the engine cell.
RECORDED = pathlib.Path(__file__).resolve().parents[1] / "recorded"


def _reader(name):
    from bench.tests import harness
    return harness.load_run(harness.ROOT).load_reader(name)


def test_recorded_training_step():
    from bench import counts
    t = trace.read(RECORDED / "train.granite3-2b.lead2.1chip.step.json.gz")
    p = "/device:TPU:0"
    assert list(t["devices"]) == [p]
    assert trace.window_ns(t) == 269_928_390
    assert trace.busy_ns(t, p) == 269_718_822
    assert trace.collective_ns(t, p) == 0
    reader = _reader("lead_update_roofline.train")
    # one lead_update call per parameter leaf
    events = trace.kernel_events(t, p, reader.KERNEL)
    assert len(events) == 12
    assert trace.kernel_ns(t, p, reader.KERNEL) == 28_165_045
    m = json.loads((RECORDED.parent / "configs" /
                                  "granite3-2b-L4-lead2.json").read_text())
    sizes = counts.leaf_sizes(dict(m["model"], tie_embeddings=False))
    ctx = {"trace": t, "traced_steps": 1,
           "peak": counts.peaks("TPU v5 lite"),
           "lead_update_bytes_per_step_per_chip": sum(
               counts.lead_update_bytes(counts.blocks(n, 512), 512)
               for n in sizes),
           "lead_update_calls_per_step_per_chip": len(sizes)}
    share = reader.read(ctx)
    assert 80.0 < share < 90.0       # 19.56 GB in 28.17 ms at 819 GB/s
    assert trace.top_ops(t, 1)[0][0].startswith("step.23 tpu_custom_call")


def test_recorded_engine_steps():
    t = trace.read(RECORDED / "engine.lead2.ring8.d16M.2steps.json.gz")
    p = "/device:TPU:0"
    # the engine's lead_update reader is the training cell's
    lu, enc = (_reader(n).KERNEL for n in ("lead_update_roofline.train",
                                           "diff_encode_roofline.engine"))
    from bench import counts
    rows = 8 * 2 ** 24 // 512
    ctx = {"trace": t, "traced_steps": 2, "peak": counts.peaks("TPU v5 lite"),
           "lead_update_bytes_per_step_per_chip":
               counts.lead_update_bytes(rows, 512),
           "lead_update_calls_per_step_per_chip": 1}
    share = _reader("lead_update_roofline.engine").read(ctx)
    assert share == _reader("lead_update_roofline.train").read(ctx)
    assert 80.0 < share < 90.0    # 11.8 GB in 17.3 ms at 819 GB/s
    assert len(trace.kernel_events(t, p, lu)) == 2
    assert len(trace.kernel_events(t, p, enc)) == 2
    assert trace.kernel_ns(t, p, lu) == 17_294_458
    assert trace.kernel_ns(t, p, enc) == 7_995_569
    assert 0.0 <= trace.idle_share(t) < 0.01
