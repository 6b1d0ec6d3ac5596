import os

import pytest

from harness import xplane_reduce as xr
from harness.xplane_reduce import Event, Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "v5e_split_tail.xplane.pb")
NS = 1e-9


def test_recorded_trace_gives_the_hand_computed_numbers():
    """fixtures/v5e_split_tail.xplane.pb is 5.3 ms of device 0's `XLA Ops` line
    from a traced run of the 10.5M-row trainer on a TPU v5e (my chip run, PR
    24), cut to the 27 events longer than 1 us plus the two `bench:chunk`
    annotations: one `cond` (1,212,353 ns) whose 20 children include the
    `split_stream` Mosaic kernel (1,129,970 ns), then six small ops, then two
    whole-matrix copies (2,045,253 and 2,043,595 ns).  There is no
    `bench:window` in it, so the window is the span of the events."""
    trace = xr.read(FIXTURE)
    assert list(trace.device) == [0] and len(trace.device[0]) == 27
    assert [h.name for h in trace.host] == ["bench:chunk", "bench:chunk"]
    r = xr.reduce(trace)
    # window: start of the cond (1,615,999,811) to the end of the last copy (1,621,313,791)
    assert r["window_s"] == pytest.approx(5_313_980 * NS)
    # the 26 leaves do not overlap; their durations sum to 5,289,338 ns
    assert r["launches"] == 26 and r["chips"] == 1
    assert r["busy_s"] == pytest.approx(5_289_338 * NS)
    assert r["mosaic_s"] == pytest.approx(1_129_970 * NS)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    copies = "copy s32[16,10501024]"
    assert r["leaf_op_s"][copies] == pytest.approx((2_045_253 + 2_043_595) * NS)
    assert r["op_launches"][copies] == 2
    assert r["op_launches"]["copy f32[2,28,62,1]"] == 9
    # the cond is no leaf: its own time is what its children (1,194,637 ns) leave
    cond = "cond (s32[16,10501024], s32[2,2], f32[2,8], f32[2,8], s32[2])"
    assert cond not in r["leaf_op_s"]
    assert r["op_self_s"][cond] == pytest.approx(17_716 * NS)
    # idle: 5,313,980 - 5,289,338 ns, all of it while the host was in bench:chunk
    assert r["idle_gaps_s"] == {"bench:chunk": pytest.approx(24_642 * NS)}
    assert xr.breakdown(r)["device_ops"][0][0] == copies


def test_window_clipping_nesting_collectives_and_chips():
    window = Event(xr.WINDOW, 10.0, 20.0)
    chip0 = [
        Event("%while.1 = (s32[8]) while(s32[8] %p)", 9.0, 19.0),  # parent, starts before the window
        Event("%fusion.1 = f32[4]{0} fusion(f32[4] %a)", 9.5, 11.0),  # clipped to 10..11
        Event("%psum.3 = f32[4]{0} all-reduce-start(f32[4] %b)", 12.0, 12.1),  # named by JAX
        Event("%fusion.2 = f32[4]{0} fusion(f32[4] %c)", 13.0, 13.5),  # hides 0.5 s of the all-reduce
        Event("%all-reduce-done.3 = f32[4]{0} all-reduce-done(f32[4] %s)", 13.5, 14.0),
        Event("fusion.7", 21.0, 22.0),  # after the window
    ]
    async0 = [Event("%psum.3 = f32[4]{0} all-reduce-start(f32[4] %b)", 12.0, 14.0)]
    chip1 = [Event("%fusion.1 = f32[4]{0} fusion(f32[4] %a)", 10.0, 20.0)]
    host = [window, Event("bench:chunk", 10.0, 15.0)]
    extra = [Event("records_fetch", 11.0, 15.0)]
    r = xr.reduce(Trace({0: chip0, 1: chip1}, {0: async0}, host), extra)
    assert r["window_s"] == 10.0 and r["chips"] == 2
    # chip 0 is busy 10..11, 12..12.1 and 13..14 (2.1 s), chip 1 all 10 s
    assert r["busy_s"] == pytest.approx((2.1 + 10.0) / 2)
    assert r["launches"] == pytest.approx((4 + 1) / 2)
    # the all-reduce lasts 12..14 on chip 0; fusion.2 covers 0.5 s of it
    assert r["collective_s"] == pytest.approx(2.0 / 2)
    assert r["collective_exposed_s"] == pytest.approx(1.5 / 2)
    assert r["leaf_op_s"]["fusion f32[4]"] == pytest.approx((1.0 + 0.5 + 10.0) / 2)
    # the while's own time inside the window: 10..19 less its children's 2.1 s
    assert r["op_self_s"]["while (s32[8])"] == pytest.approx((9.0 - 2.1) / 2)
    # chip 0 idles 11..12 and 12.1..13 (records_fetch, the innermost span) and
    # 14..20; a gap goes whole to the span open at its middle (17: none)
    assert r["idle_gaps_s"] == {"records_fetch": pytest.approx((1.0 + 0.9) / 2),
                                "(no host span)": pytest.approx(6.0 / 2)}


def test_op_label_and_errors():
    assert xr.op_label('%copy.2191 = s32[16,10501024]{1,0:T(8,128)} copy(s32[16,10501024]{1,0:T(8,128)} %g.2)') \
        == ("copy s32[16,10501024]", "copy")
    assert xr.op_label("%psum.7 = f32[256,16,1764]{2,1,0:T(8,128)} all-reduce(f32[256,16,1764]{2,1,0} %x), channel_id=3") \
        == ("psum f32[256,16,1764]", "all-reduce")
    assert xr.op_label("fusion.12") == ("fusion", "fusion")
    with pytest.raises(ValueError):
        xr.reduce(Trace({}, {}, []))
    with pytest.raises(FileNotFoundError):
        xr.find_xplane(os.path.dirname(__file__))
