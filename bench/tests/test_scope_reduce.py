"""bench/scopes.py: the wire decoder on the recorded v5e trace, the scope
reduction on a hand-built trace with hand-computed numbers, and on a small
trace of named-scope loops recorded on a TPU v5e (``record_scan_trace.py``)."""

import shutil
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import run as R
from bench import scopes as S
from bench import trace_reduce as TR

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "v5e_small.xplane.pb"
SCAN = DATA / "v5e_scan.xplane.pb"
READERS = ("full_step_ms.sd", "cond_step_ms.sd", "self_attn_ms.sd",
           "resblock_ms.sd", "passes_per_image.sd")


# -- a protobuf writer, just enough for an XSpace ------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, meta, strings=()):
    """``lines``: {line: [(metadata id, start ns, end ns)]}; ``meta``: {id:
    (name, tf_op)}, a tf_op given as a stat string, or by reference where
    it is one of ``strings`` (stat metadata ids 2, 3, ...)."""
    stat_meta = {1: "tf_op", **{2 + i: s for i, s in enumerate(strings)}}
    out = [(2, name)]
    for i, (line, evs) in enumerate(lines.items()):
        events = [(4, _msg((1, m), (2, s * 1000), (3, (e - s) * 1000)))
                  for m, s, e in evs]
        out.append((3, _msg((1, i), (2, line), (3, 0), *events)))
    for mid, (n, tf_op) in meta.items():
        stats = []
        if tf_op in strings:
            stats = [(5, _msg((1, 1), (7, 2 + strings.index(tf_op))))]
        elif tf_op:
            stats = [(5, _msg((1, 1), (5, tf_op)))]
        out.append((4, _msg((1, mid), (2, _msg((1, mid), (2, n), *stats)))))
    for sid, n in stat_meta.items():
        out.append((5, _msg((1, sid), (2, _msg((1, sid), (2, n))))))
    return _msg(*out)


# Times in ns, window [1000, 11000]; one program run [1000, 7500]. A FULL
# loop [1000, 5000] runs attn (500 ns) and update (200 ns) twice, with a
# zero-length op starting as the second attn does (it holds no time, and
# the attn op is no container for it); a
# cross-attention op hoisted out of it [5000, 5200]; a COND loop [5500,
# 7000] runs a resblock op once; an unscoped op [7000, 7500]; then the
# encoder op [9500, 10000], its tf_op stored by reference. Gaps: inside
# the run [1700, 2000] before attn, [2700, 5000] before the hoisted op,
# [5200, 5500] before the resblock, [6000, 7000] before the unscoped op;
# outside it [7500, 9500] in sd.generate and [10000, 11000] in bench.batch.
BODY = "jit(run)/while/body/closed_call"
ENCODE = "jit(enc)/sd.encode/dot_general:"
XSPACE = _msg(
    (1, _plane("/device:TPU:0", {
        "XLA Ops": [(10, 1000, 5000), (1, 1000, 1500), (2, 1500, 1700),
                    (7, 2000, 2000), (1, 2000, 2500), (2, 2500, 2700), (3, 5000, 5200),
                    (11, 5500, 7000), (4, 5500, 6000), (5, 7000, 7500),
                    (6, 9500, 10000)],
        "XLA Modules": [(20, 1000, 7500)]}, {
        1: ("%fusion.1", f"{BODY}/sd.step.full/unet/unet.down.0/unet.attn.self/dot_general:"),
        2: ("%fusion.2", f"{BODY}/sd.step.full/sd.update/add:"),
        3: ("%fusion.3", "jit(run)/sd.step.full/unet/unet.mid/unet.attn.cross/dot_general:"),
        4: ("%fusion.4", f"{BODY}/sd.step.cond/unet/unet.up.1/unet.res/conv_general_dilated:"),
        5: ("%fusion.5", "jit(run)/while/body/add:"),
        6: ("%fusion.6", ENCODE),
        7: ("%slice-done.1", None),
        10: ("%while.1 = (s32[]) while(%t)", "jit(run)/while"),
        11: ("%while.2 = (s32[]) while(%u)", "jit(run)/while"),
        20: ("jit_run", None)}, strings=(ENCODE,))),
    (1, _plane("/host:CPU", {
        "python": [(1, 1000, 11000), (2, 900, 11000), (3, 7600, 9400)]}, {
        1: ("bench.window", None), 2: ("bench.batch", None), 3: ("sd.generate", None)})),
)


@pytest.fixture(scope="module")
def handmade():
    return S.reduce([S.Plane(v) for f, v in S.fields(XSPACE) if f == 1])


def test_scope_times(handmade):
    r = handmade
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(3100e-9)
    time = {k: v["time_s"] for k, v in r["scopes"].items()}
    assert time == pytest.approx({
        "sd.step.full": 1600e-9, "sd.step.cond": 500e-9, "sd.update": 400e-9,
        "sd.encode": 500e-9, "unet": 1700e-9, "unet.down.0": 1000e-9,
        "unet.mid": 200e-9, "unet.up.1": 500e-9, "unet.attn.self": 1000e-9,
        "unet.attn.cross": 200e-9, "unet.res": 500e-9})
    own = {k: v["self_s"] for k, v in r["scopes"].items() if v["self_s"]}
    assert own == pytest.approx({
        "unet.attn.self": 1000e-9, "sd.update": 400e-9, "unet.attn.cross": 200e-9,
        "unet.res": 500e-9, "sd.encode": 500e-9})
    assert r["unscoped_s"] == pytest.approx(500e-9)
    assert sum(own.values()) + r["unscoped_s"] == pytest.approx(r["busy_s"])


def test_step_runs_count_loop_ops_only(handmade):
    # the hoisted op ran once, outside the loop: it does not count
    assert handmade["steps"] == {"sd.step.full": 2, "sd.step.cond": 1}


def test_gaps_named_in_program_or_by_host_span(handmade):
    assert handmade["gap_s_by_name"] == pytest.approx({
        "in:unet.attn.self": 300e-9, "in:unet.attn.cross": 2300e-9,
        "in:unet.res": 300e-9, "in:no scope": 1000e-9,
        "sd.generate": 2000e-9, "bench.batch": 1000e-9})
    assert handmade["top_gaps"][0] == ["in:unet.attn.cross", pytest.approx(2300e-9)]


def test_disagreeing_body_ops_give_no_count():
    xs = XSPACE.replace(_msg((1, 2), (2, 2500 * 1000), (3, 200 * 1000)),
                        _msg((1, 7), (2, 2500 * 1000), (3, 200 * 1000)))
    r = S.reduce([S.Plane(v) for f, v in S.fields(xs) if f == 1])
    assert r["steps"]["sd.step.full"] is None  # op 1 ran twice, op 2 once


@pytest.mark.parametrize("tf_op, path", [
    ("jit(<lambda>)/dot_general:", []),
    (f"{BODY}/sd.step.full/unet/unet.up.3/unet.res/conv_general_dilated:convolution",
     ["sd.step.full", "unet", "unet.up.3", "unet.res"]),
    ("jit(run)/sd.combine/sub", ["sd.combine"]),
    (None, []),
])
def test_scope_path(tf_op, path):
    assert S.scope_path(tf_op) == path


# -- the recorded v5e traces ---------------------------------------------------


def test_decoder_reads_tf_op_on_recorded_trace():
    [dev] = [p for p in S.read_xspace(RECORDED) if p.name == "/device:TPU:0"]
    by_name = {n.split(" = ")[0]: st for n, st in dev.meta.values()}
    assert by_name["%fusion"]["tf_op"] == "jit(<lambda>)/dot_general:"
    assert by_name["%fusion.1"]["tf_op"].startswith("jit(<lambda>)/")
    assert isinstance(by_name["%fusion"]["program_id"], int)


def test_decoder_matches_trace_reduce_on_recorded_trace():
    r = S.reduce(S.read_xspace(RECORDED))
    t = TR.reduce(ProfileData.from_file(str(RECORDED)))
    assert r["busy_s"] == t["busy_s"]
    assert r["window_s"] == t["window_s"]
    assert r["unscoped_s"] == pytest.approx(t["busy_s"])
    assert r["steps"] == {"sd.step.full": 0, "sd.step.cond": 0}


def test_recorded_loops_count_and_attribute():
    """On the chip a body's product, tanh and update fuse into one op
    under ``unet``; the loop's carry copy is named by the ``while`` only."""
    r = S.reduce(S.read_xspace(SCAN))
    assert r["steps"] == {"sd.step.full": 5, "sd.step.cond": 3}
    sc = r["scopes"]
    full, cond = sc["sd.step.full"]["time_s"], sc["sd.step.cond"]["time_s"]
    assert sc["unet"]["time_s"] == pytest.approx(full + cond)
    # a COND step runs half the rows: half the time of a FULL step
    assert (cond / 3) / (full / 5) == pytest.approx(0.5, abs=0.02)
    owned = sum(v["self_s"] for v in sc.values()) + r["unscoped_s"]
    assert owned == pytest.approx(r["busy_s"])
    assert max(r["gap_s_by_name"], key=r["gap_s_by_name"].get) == "bench.sleep"


# -- the readers ---------------------------------------------------------------


def _read_all(trace_dir, monkeypatch, **ctx):
    monkeypatch.setattr(R, "TRACE_DIR", trace_dir)
    ctx = {"chips": 1, **ctx}
    return {m: R.load_module(R.BENCH / "layer_metrics" / f"{m}.py", "t_" + m.replace(".", "_")
                             ).read(ctx) for m in READERS}


def test_readers_on_handmade_trace(tmp_path, monkeypatch):
    (tmp_path / "h.xplane.pb").write_bytes(XSPACE)
    got = _read_all(tmp_path, monkeypatch, counters={"images": 2}, traffic={"batch": 2})
    assert got == pytest.approx({
        "full_step_ms.sd": 800e-6, "cond_step_ms.sd": 500e-6,
        "self_attn_ms.sd": 500e-6, "resblock_ms.sd": 250e-6,
        "passes_per_image.sd": 5.0})


@pytest.mark.parametrize("content", ["recorded", "garbage"])
def test_readers_give_nothing_without_scopes(tmp_path, monkeypatch, content):
    """A program without the scopes (or an unreadable trace) reads as no
    metric, and raises nothing."""
    path = tmp_path / "t.xplane.pb"
    if content == "recorded":
        shutil.copy(RECORDED, path)
    else:
        path.write_bytes(b"\xff" * 64)
    got = _read_all(tmp_path, monkeypatch, counters={"images": 4}, traffic={"batch": 4})
    assert got == dict.fromkeys(READERS)
