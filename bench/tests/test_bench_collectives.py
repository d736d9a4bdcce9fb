"""The collective readers (bench/collectives.py) on hand-built traces whose
events carry their instructions' compiled text."""
import pytest

from bench import collectives

MS = 1_000_000
P0, P1 = "/device:TPU:0", "/device:TPU:1"
CODES = "s8[1,8192,512]{2,1,0:T(8,128)(4,1)S(1)}"
SCALES = "f32[1,1,4]{2,1,0:T(1,128)}"


def _start(i, buf):
    return (f"%collective-permute-start.{i} = ({buf}, {buf}, u32[]{{:S(2)}}, "
            f"u32[]{{:S(2)}}) collective-permute-start(%)")


def _done(i, buf):
    return f"%collective-permute-done.{i} = {buf} collective-permute-done(%)"


FUSION = "%fusion.1 = f32[8]{0} fusion(%, %)"


def test_a_buffer_takes_the_bytes_its_layout_keeps():
    assert collectives.buffer_bytes(CODES) == 8192 * 512
    # the last dim of 4 padded to 128 lanes
    assert collectives.buffer_bytes(SCALES) == 128 * 4
    # minor-to-major {1,2,0}: 8192 is the minor dim, so nothing pads
    assert collectives.buffer_bytes(
        "f32[1,8192,4]{1,2,0:T(4,128)S(1)}") == 8192 * 4 * 4
    assert collectives.buffer_bytes("s8[1,1,2048]{2,1,0:T(4,128)(4,1)}") \
        == 4 * 2048
    assert collectives.buffer_bytes("f32[]{:T(128)}") == 128 * 4
    assert collectives.buffer_bytes("u32[]") == 4
    assert collectives.buffer_bytes("bf16[3,5]{1,0}") == 30
    with pytest.raises(ValueError):
        collectives.buffer_bytes("(f32[2])")


def test_only_a_start_or_a_whole_collective_sends():
    assert collectives.sent_bytes(_start(3, CODES)) == 8192 * 512
    assert collectives.sent_bytes(_done(3, CODES)) == 0
    assert collectives.sent_bytes(FUSION) == 0
    assert collectives.sent_bytes(
        "%all-reduce.20 = f32[]{:T(128)} all-reduce(%)") == 512
    assert collectives.sent_bytes("window") == 0


def _two_chips():
    """Two traced steps.  Chip 0: codes in flight 30-60 ms, a fusion until
    40 and another 50-55 hide 15 of them; scales 70-72 ms with nothing
    beside them.  Chip 1: the same two collectives in 10-20 ms, wholly
    under a fusion.  Each chip sends its codes and scales once."""
    ops0 = [(FUSION, 0, 40 * MS), (_start(1, CODES), 30 * MS, 31 * MS),
            (FUSION, 50 * MS, 55 * MS), (_done(1, CODES), 60 * MS, 61 * MS),
            (_start(2, SCALES), 70 * MS, 70 * MS + 1),
            (_done(2, SCALES), 72 * MS - 1, 72 * MS)]
    async0 = [(_start(1, CODES), 30 * MS, 61 * MS),
              (_start(2, SCALES), 70 * MS, 72 * MS)]
    ops1 = [(FUSION, 0, 90 * MS), (_start(1, CODES), 10 * MS, 11 * MS),
            (_done(1, CODES), 19 * MS, 20 * MS),
            (_start(2, SCALES), 12 * MS, 13 * MS),
            (_done(2, SCALES), 14 * MS, 15 * MS)]
    async1 = [(_start(1, CODES), 10 * MS, 20 * MS),
              (_start(2, SCALES), 12 * MS, 15 * MS)]
    return {"devices": {P0: ops0, P1: ops1}, "async": {P0: async0, P1: async1},
            "host": [], "window": (0, 100 * MS)}


def _reader(name):
    from bench.tests import harness
    return harness.load_run(harness.ROOT).load_reader(name)


def _ctx(t, steps=2, metered=None):
    return {"trace": t, "traced_steps": steps,
            "metered_wire_bytes_per_step_per_chip": metered}


def test_collective_time_is_the_ops_own_per_step_averaged_over_chips():
    t = _two_chips()
    # chip 0: the halves 30-31, 60-61 and 2 ns of the scales' halves; chip
    # 1: 10-11, 12-13, 14-15, 19-20; two steps.  Flights do not count.
    assert _reader("collective_ms.train").read(_ctx(t)) == \
        pytest.approx((2 + 2e-6 + 4) / 2 / 2)
    # the same without the async line, which the profiler records for the
    # first chip alone
    t["async"] = {P0: t["async"][P0]}
    assert _reader("collective_ms.train").read(_ctx(t)) == \
        pytest.approx((2 + 2e-6 + 4) / 2 / 2)


def test_the_exposed_share_leaves_out_what_other_ops_cover():
    t = _two_chips()
    # chip 0: 40-50, 55-61 and 70-72 of the window's 100 ms; chip 1: none
    assert _reader("exposed_collective_share.train").read(_ctx(t)) == \
        pytest.approx((18 / 100 * 100 + 0) / 2)


def test_wire_bytes_are_read_from_each_start_over_the_metered_payload():
    t = _two_chips()
    sent = 8192 * 512 + 128 * 4            # per chip, in the two steps
    metered = 8192 * 512 * 3 / 8 * 2       # 3 bits an element, 2 neighbours
    got = _reader("wire_bytes_ratio.train").read(_ctx(t, metered=metered))
    assert got == pytest.approx(sent / 2 / metered)


def test_no_collective_reads_none():
    t = {"devices": {P0: [(FUSION, 0, 10 * MS)]}, "async": {},
         "host": [], "window": (0, 20 * MS)}
    for name in ("collective_ms.train", "exposed_collective_share.train",
                 "wire_bytes_ratio.train"):
        assert _reader(name).read(_ctx(t, metered=1.0)) is None
        assert _reader(name).read(_ctx(None, metered=1.0)) is None
    # a ring of one meters nothing to send
    assert _reader("wire_bytes_ratio.train").read(
        _ctx(_two_chips(), metered=0.0)) is None
