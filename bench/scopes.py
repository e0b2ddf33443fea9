"""Attribute a profiler trace's device time to the program's named scopes.

The program names its layers with ``jax.named_scope`` (``sd.step.full``,
``unet.attn.self``, ...; DESIGN.md lists them). On a TPU the trace keeps
each op's JAX name stack in the ``tf_op`` stat of the op's event metadata,
e.g. ``jit(run)/while/body/closed_call/sd.step.full/unet/unet.down.0/
unet.res/conv_general_dilated:``. ``jax.profiler.ProfileData`` does not
expose event-metadata stats, so this module reads the ``.xplane.pb``
itself with a plain protobuf wire decoder (no protobuf package):

    XSpace.planes (1) -> XPlane {name (2), lines (3), event_metadata (4),
    stat_metadata (5)}; XLine {name (2), timestamp_ns (3), events (4)};
    XEvent {metadata_id (1), offset_ps (2), duration_ps (3), stats (4)};
    XEventMetadata {id (1), name (2), stats (5)};
    XStat {metadata_id (1), int (3, 4), str_value (5), ref_value (7)}.

The window, the device planes and the clock alignment are those of
``bench/trace_reduce.py``. Its leaf ops are not: there an op inside which
another op starts is a container and is left out, and on a v5e that
drops real ops whenever a zero-length op (an async ``slice-done``) starts
in the same nanosecond. Here only ``while`` ops are containers; no other
ops of a v5e trace overlap, so the ops' times add up to the busy union.
Each op's time goes to every listed scope in its stack (``time_s``) and
to the innermost one (``self_s``); an op with none is unscoped. A step
body's run count is the count of each of its ops that ran inside a loop
(ops that XLA hoisted out of the loop run once a loop and do not count):
the same for all of them, else ``None``.

The device's idle gaps are named as the benchmark's reduction names them,
with the program's own host spans (``sd.``) among the candidates, except
that a gap inside a program run (within its ``XLA Modules`` event) is
named ``in:<scope>`` by the op that follows it: host time between programs
and loop overhead inside one are told apart.
"""

from __future__ import annotations

import bisect
import re
import struct
import sys
from collections import defaultdict

from bench import trace_reduce as TR

#: The program's scopes; a level scope is ``unet.down.<n>`` / ``unet.up.<n>``.
SCOPES = frozenset({
    "sd.encode", "sd.step.full", "sd.step.cond", "sd.combine", "sd.update",
    "unet", "unet.time", "unet.io", "unet.mid", "unet.res", "unet.resample",
    "unet.attn.norm", "unet.attn.self", "unet.attn.cross"})
LEVEL = re.compile(r"unet\.(down|up)\.\d+$")
STEP_SCOPES = ("sd.step.full", "sd.step.cond")
HOST_SPAN_PREFIXES = TR.HOST_SPAN_PREFIXES + ("sd.",)
UNSCOPED = "no scope"
#: An op whose event holds its body's ops: a loop.
CONTAINER = re.compile(r"\bwhile\(")


# -- wire format ---------------------------------------------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wt == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(raw, stat_names):
    """XStat messages -> {stat name: int or str}. A string stat may be
    stored once, as the name of the stat metadata it refers to."""
    out = {}
    for buf in raw:
        mid, val = None, None
        for f, v in fields(buf):
            if f == 1:
                mid = v
            elif f in (3, 4):
                val = _signed(v)
            elif f == 5:
                val = bytes(v).decode("utf-8", "replace")
            elif f == 7:
                val = stat_names.get(v)
        if mid in stat_names:
            out[stat_names[mid]] = val
    return out


def _run_id(stat, run_id, default):
    for f, v in fields(stat):
        if f == 1 and v != run_id:
            return default
        if f in (3, 4):
            return _signed(v)
    return default


class Plane:
    """One XPlane: ``name``; ``lines`` as {line name: [(start ns, end ns,
    metadata id, run_id or None)]}; ``meta`` as {metadata id: (name, stats)}."""

    def __init__(self, buf):
        self.name, raw_lines, raw_meta, raw_stat = "", [], [], []
        for f, v in fields(buf):
            if f == 2:
                self.name = bytes(v).decode()
            elif f == 3:
                raw_lines.append(v)
            elif f == 4:
                raw_meta.append(v)
            elif f == 5:
                raw_stat.append(v)
        stat_names = {}
        for entry in raw_stat:
            for f, v in fields(entry):
                if f == 2:
                    sid, name = None, ""
                    for g, w in fields(v):
                        if g == 1:
                            sid = w
                        elif g == 2:
                            name = bytes(w).decode()
                    stat_names[sid] = name
        self.meta = {}
        for entry in raw_meta:
            for f, v in fields(entry):
                if f == 2:
                    mid, name, st = None, "", []
                    for g, w in fields(v):
                        if g == 1:
                            mid = w
                        elif g == 2:
                            name = bytes(w).decode("utf-8", "replace")
                        elif g == 5:
                            st.append(w)
                    self.meta[mid] = (name, _stats(st, stat_names))
        run_id = next((k for k, n in stat_names.items() if n == "run_id"), None)
        self.lines = {}
        for buf in raw_lines:
            name, t0, evs = "", 0, []
            for f, v in fields(buf):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 3:
                    t0 = v
                elif f == 4:
                    evs.append(v)
            out = self.lines.setdefault(name, [])
            for ev in evs:
                mid, off, dur, rid = 0, 0, 0, None
                for f, v in fields(ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                    elif f == 4 and run_id is not None:
                        rid = _run_id(v, run_id, rid)
                # whole ns, as ``ProfileData`` gives them
                s = t0 + off // 1000
                out.append((s, s + dur // 1000, mid, rid))

    def event_name(self, mid) -> str:
        return self.meta.get(mid, ("", {}))[0]


def read_xspace(path) -> list[Plane]:
    with open(path, "rb") as f:
        data = f.read()
    return [Plane(v) for f, v in fields(data) if f == 1]


# -- scopes --------------------------------------------------------------------


def scope_path(tf_op: str | None) -> list[str]:
    """The listed scopes in a ``tf_op`` name stack, outermost first."""
    if not tf_op:
        return []
    stack = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return [c for c in stack.split("/") if c in SCOPES or LEVEL.match(c)]


def _host_and_devices(planes):
    """-> (host spans [(start, end, name)], {device plane: (plane, ops
    [(start, end, mid)], modules [(start, end)])}), device times moved
    onto the host clock as ``trace_reduce.planes_of`` moves them."""
    host, enqueued, completed, devices = [], {}, {}, {}
    for p in planes:
        if p.name.startswith("/device:") and "host" not in p.name.lower():
            devices[p.name] = p
        elif p.name.startswith("/host:"):
            for evs in p.lines.values():
                for s, e, mid, rid in evs:
                    name = p.event_name(mid)
                    if name.startswith(HOST_SPAN_PREFIXES):
                        host.append((s, e, name))
                    elif name in ("DoEnqueueProgram", "CompleteCallbacks") and rid is not None:
                        side = enqueued if name == "DoEnqueueProgram" else completed
                        side.setdefault(rid, s)
    out = {}
    for name, p in devices.items():
        mods = p.lines.get(TR.MODULES_LINE, [])
        off = TR.clock_offset({rid: (s, e) for s, e, _, rid in mods if rid is not None},
                              enqueued, completed)
        ops = [(s + off, e + off, mid) for s, e, mid, _ in p.lines.get(TR.OPS_LINE, [])]
        out[name] = (p, ops, [(s + off, e + off) for s, e, _, _ in mods])
    return host, out


def _inside(intervals, t) -> bool:
    """Whether t lies in one of the merged, sorted ``intervals``."""
    k = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return k >= 0 and intervals[k][0] <= t <= intervals[k][1]


def reduce(planes, *, n_devices: int = 1, top: int = 10) -> dict:
    """Per-scope device time and step-body run counts in the window."""
    host, devices = _host_and_devices(planes)
    win = [(s, e) for s, e, n in host if n == TR.WINDOW]
    if not win:
        raise ValueError(f"no {TR.WINDOW!r} span in the trace")
    lo, hi = win[0]
    used = [n for n in sorted(devices, key=TR._device_index)
            if devices[n][1]][:n_devices]
    if not used:
        raise ValueError("no device plane with XLA ops in the trace")
    time_s, self_s = defaultdict(float), defaultdict(float)
    counts = {s: defaultdict(int) for s in STEP_SCOPES}
    busy_total, gap_named = 0.0, []
    for dev in used:
        plane, ops, mods = devices[dev]
        loops, window_ops, paths = [], [], {}
        for s, e, mid in ops:
            if CONTAINER.search(plane.event_name(mid)):
                loops.append((s, e))
            elif e > lo and s < hi:
                window_ops.append((s, e, mid))
        loops = TR.union(loops)
        for s, e, mid in window_ops:
            if mid not in paths:
                paths[mid] = scope_path(plane.meta.get(mid, ("", {}))[1].get("tf_op"))
            path = paths[mid]
            d = min(e, hi) - max(s, lo)
            for sc in set(path):
                time_s[sc] += d
            self_s[path[-1] if path else UNSCOPED] += d
            for sc in STEP_SCOPES:
                if sc in path and _inside(loops, (s + e) / 2):
                    counts[sc][dev, mid] += 1
        busy = TR.union(TR.clip([(s, e) for s, e, _ in window_ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        if dev == used[0]:
            gap_named = _name_gaps(TR.gaps(busy, lo, hi), window_ops, paths,
                                   TR.union(mods), host)
    k = len(used)
    steps = {}
    for sc, per_op in counts.items():
        runs = set(per_op.values())
        steps[sc] = runs.pop() if len(runs) == 1 else (None if runs else 0)
    by_name = defaultdict(float)
    for d, n in gap_named:
        by_name[n] += d / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / k / 1e9,
        "scopes": {sc: {"time_s": time_s[sc] / k / 1e9,
                        "self_s": self_s.get(sc, 0.0) / k / 1e9}
                   for sc in sorted(time_s)},
        "unscoped_s": self_s.get(UNSCOPED, 0.0) / k / 1e9,
        "steps": steps,
        "op_runs": {sc: sorted(set(v.values())) for sc, v in counts.items()},
        "gap_s_by_name": dict(by_name),
        "top_gaps": [[n, d / 1e9] for d, n in sorted(gap_named, reverse=True)[:top]],
    }


def _name_gaps(gaps, ops, paths, runs, host):
    """[(length ns, name)] for each idle gap: ``in:<scope>`` of the next op
    inside a program run, else the innermost host span."""
    by_start = sorted(ops, key=lambda op: (op[0], op[0] - op[1]))  # longest first
    starts = [s for s, _, _ in by_start]
    out = []
    for s, e in gaps:
        mid_t = (s + e) / 2
        if _inside(runs, mid_t):
            k = bisect.bisect_left(starts, e)
            path = paths.get(by_start[k][2], []) if k < len(by_start) else []
            out.append((e - s, "in:" + (path[-1] if path else UNSCOPED)))
        else:
            out.append((e - s, TR.innermost(host, mid_t)))
    return out


_CACHE: dict = {}


def reduce_dir(trace_dir, *, n_devices: int = 1) -> dict:
    """``reduce`` of the newest trace under ``trace_dir``, once per file;
    the first call prints the per-scope totals on standard error."""
    path = TR.find_xplane(trace_dir)
    if path not in _CACHE:
        _CACHE[path] = r = reduce(read_xspace(path), n_devices=n_devices)
        print(summary(r), file=sys.stderr)
    return _CACHE[path]


def summary(r: dict) -> str:
    lines = [f"scopes: busy {r['busy_s']:.6f} s of {r['window_s']:.6f} s, "
             f"steps {r['steps']} (runs of the body ops {r['op_runs']}), "
             f"unscoped {r['unscoped_s']:.6f} s"]
    for sc, v in sorted(r["scopes"].items(), key=lambda kv: -kv[1]["time_s"]):
        lines.append(f"scope {sc}: {v['time_s']:.6f} s (innermost {v['self_s']:.6f} s)")
    for n, t in sorted(r["gap_s_by_name"].items(), key=lambda kv: -kv[1]):
        lines.append(f"idle {n}: {t:.6f} s")
    return "\n".join(lines)


def for_run(ctx: dict) -> dict | None:
    """The scope reduction of a traced benchmark run, for a per-layer
    reader; ``None`` where the trace names no step scope (a program
    without the scopes) or cannot be read (the error goes to standard
    error)."""
    from bench.run import TRACE_DIR
    try:
        r = reduce_dir(TRACE_DIR, n_devices=ctx["chips"])
    except Exception as e:  # a reader returns nothing rather than raise
        print(f"scopes: no reduction: {e!r}", file=sys.stderr)
        return None
    return r if any(sc in r["scopes"] for sc in STEP_SCOPES) else None


def step_ms(ctx: dict, scope: str) -> float | None:
    """Device time under a step scope over the steps it ran, in ms."""
    r = for_run(ctx)
    n = r and r["steps"].get(scope)
    if not n or scope not in r["scopes"]:
        return None
    return 1e3 * r["scopes"][scope]["time_s"] / n


def per_image_ms(ctx: dict, scope: str) -> float | None:
    """Device time under ``scope`` over the images finished, in ms."""
    r, images = for_run(ctx), ctx["counters"].get("images")
    if r is None or not images or scope not in r["scopes"]:
        return None
    return 1e3 * r["scopes"][scope]["time_s"] / images


def passes_per_image(ctx: dict) -> float | None:
    """UNet passes run per image: (2 x FULL steps + COND steps) x batch
    over the images finished. A FULL step runs the batch's rows twice."""
    r, images = for_run(ctx), ctx["counters"].get("images")
    if r is None or not images:
        return None
    full, cond = (r["steps"].get(sc) for sc in STEP_SCOPES)
    if full is None or cond is None:
        return None
    return (2 * full + cond) * int(ctx["traffic"]["batch"]) / images
