"""bench/trace_reduce.py on a hand-built trace with hand-computed numbers,
and on a small trace recorded on a TPU v5e (``record_trace.py``)."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as TR

DATA = Path(__file__).parent / "data"


def _events(line_id, name, t0, evs):
    body = "".join(f"    events {{ metadata_id: {m} offset_ps: {(s - t0) * 1000} "
                   f"duration_ps: {(e - s) * 1000} }}\n" for m, s, e in evs)
    return f'  lines {{ id: {line_id} name: "{name}" timestamp_ns: {t0}\n{body}  }}\n'


def _meta(names):
    return "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in names.items())


# times in ns. Window [1000, 11000]. Device 0 ops: a while loop [1000,
# 7000] holding fusion.1 [1000, 3000], fusion.2 [3000, 4000] and the kernel
# [6000, 7500] (ends after the loop's end: the loop still only counts as a
# container), then fusion.1 [10500, 12000] (clipped to 11000). Busy union of
# the leaves: 3000 + 1500 + 500 = 5000. Gaps: [4000, 6000] (midpoint 5000,
# inside serve.admit [4500, 5200] and bench.tick [1000, 5500]: the
# shorter, serve.admit, names it) and [7500, 10500] (midpoint 9000, inside
# bench.sleep [7000, 10000]). Device 1: one op [1000, 2000], busy 1000.
XSPACE = (
    "planes {\n  id: 1\n  name: \"/device:TPU:0\"\n"
    + _events(1, "XLA Ops", 1000, [(5, 1000, 7000), (1, 1000, 3000), (2, 3000, 4000),
                                   (3, 6000, 7500), (1, 10500, 12000)])
    + _events(2, "XLA Modules", 1000, [(4, 1000, 7500)])
    + _meta({1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 2: "fusion.2",
             3: "%custom-call.3 = f32[8]{0} custom-call(), custom_call_target=ragged_paged_kernel",
             4: "jit_step", 5: "%while.1 = (s32[]) while(%t)"})
    + "}\nplanes {\n  id: 2\n  name: \"/device:TPU:1\"\n"
    + _events(1, "XLA Ops", 1000, [(1, 1000, 2000)])
    + _meta({1: "fusion.9"})
    + "}\nplanes {\n  id: 3\n  name: \"/host:CPU\"\n"
    + _events(1, "python", 1000, [(1, 1000, 11000), (2, 1000, 5500),
                                  (3, 4500, 5200), (4, 7000, 10000)])
    + _meta({1: "bench.window", 2: "bench.tick", 3: "serve.admit", 4: "bench.sleep"})
    + "}\n")


@pytest.fixture(scope="module")
def handmade():
    return ProfileData.from_text_proto(XSPACE)


def test_busy_idle_and_window(handmade):
    r = TR.reduce(handmade, n_devices=1, kernels={"ragged": "ragged"})
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(5000e-9)
    assert r["idle_share"] == pytest.approx(0.5)


def test_per_op_module_and_kernel_times(handmade):
    r = TR.reduce(handmade, n_devices=1, kernels={"ragged": "ragged"})
    assert r["op_time_s"] == pytest.approx({"fusion.1": 2500e-9, "fusion.2": 1000e-9,
                                            "custom-call.3": 1500e-9})
    assert [n for n, _ in r["top_ops"]] == ["fusion.1", "custom-call.3", "fusion.2"]
    assert r["kernels"]["ragged"] == {"time_s": pytest.approx(1500e-9), "calls": 1}
    assert r["modules"]["jit_step"]["time_s"] == pytest.approx(6500e-9)
    # the program run starts at 1000, inside bench.tick only
    assert r["programs_by_span"] == {"bench.tick": {"time_s": pytest.approx(6500e-9),
                                                    "calls": 1}}


def test_gaps_are_named_by_innermost_host_span(handmade):
    r = TR.reduce(handmade, n_devices=1)
    assert r["top_gaps"] == [["bench.sleep", pytest.approx(3000e-9)],
                             ["serve.admit", pytest.approx(2000e-9)]]
    assert r["host_spans"]["bench.tick"]["time_s"] == pytest.approx(4500e-9)


def test_busy_is_averaged_over_the_chips_used(handmade):
    r = TR.reduce(handmade, n_devices=2)
    assert r["busy_s"] == pytest.approx((5000e-9 + 1000e-9) / 2)


def test_no_window_is_an_error():
    xs = ProfileData.from_text_proto(XSPACE.replace("bench.window", "other"))
    with pytest.raises(ValueError):
        TR.reduce(xs)


def test_interval_helpers():
    assert TR.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert TR.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]
    assert TR.clip([(0, 3), (5, 9)], 1, 6) == [(1, 3), (5, 6)]


# The recorded v5e trace (record_trace.py), read by hand: three runs of one
# program, each four leaf ops (copy-start 13 ns, copy-done 3, fusion 11562
# or 11563, fusion.1 12613 or 12615) that never overlap; the window span is
# 64030250 ns long. On the device clock the ops precede the host calls that
# launched them by about 1.4 ms; once aligned by run_id all three runs lie
# inside the window.
RECORDED = DATA / "v5e_small.xplane.pb"
BUSY_NS = (13 + 3 + 11562 + 12613) + 2 * (13 + 3 + 11563 + 12615)


@pytest.fixture(scope="module")
def recorded():
    return ProfileData.from_file(str(RECORDED))


def test_recorded_trace_busy_and_ops(recorded):
    r = TR.reduce(recorded)
    assert r["window_s"] == pytest.approx(64030250e-9)
    assert r["busy_s"] == pytest.approx(BUSY_NS * 1e-9)
    assert r["op_time_s"]["fusion"] == pytest.approx((11562 + 2 * 11563) * 1e-9)
    assert r["op_time_s"]["fusion.1"] == pytest.approx((12613 + 2 * 12615) * 1e-9)
    assert r["modules"]["jit__lambda(14670462642265530391)"]["calls"] == 3


def test_recorded_trace_clock_alignment(recorded):
    host, devices = TR.planes_of(recorded)
    enq = [s for s, e, n in host if n == "bench.batch"]
    starts = sorted(s for s, e, n in devices["/device:TPU:0"]["XLA Modules"])
    # every run starts on the device after its host span opened, and
    # within 1 ms of it
    for h, d in zip(enq, starts):
        assert 0 < d - h < 1e6


def test_recorded_trace_gaps_named_by_sleep(recorded):
    r = TR.reduce(recorded)
    names = [n for n, _ in r["top_gaps"][:3]]
    assert names == ["bench.sleep"] * 3
    assert r["gap_s_by_span"]["bench.sleep"] > 0.06
