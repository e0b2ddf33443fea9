"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, per-op and per-program device time, kernel
time, and the device's idle gaps, each named by the host span it falls in.

Layout of a TPU trace as JAX 0.9 writes it (looked at by hand on a v5e):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
event per executed HLO op (Pallas kernels under their kernel name) and
whose line ``XLA Modules`` holds one event per program run; and the plane
``/host:CPU``, whose threads carry the host spans (``TraceAnnotation``).
Times are in nanoseconds, but the chip's clock is not the host's: on a
v5e the device events read 1.4 to 1.8 ms earlier than the host calls that
launched them. Each program run carries a ``run_id`` on both sides (the
host's ``DoEnqueueProgram`` and ``CompleteCallbacks``, the device's
``XLA Modules`` event), so the device plane is shifted by the midpoint of
the range that every run allows: after its enqueue began, before its
completion was seen.

An ``XLA Ops`` event that holds others (a ``while`` loop around its body)
is a container: it is left out, and only leaf ops count. Op names are the
HLO instruction names (``fusion.6143``). The window is the host span
``bench.window``. Busy time is the union of the leaf ops' intervals that
fall in it, averaged over the chips used.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench.window"
HOST_SPAN_PREFIXES = ("bench.", "serve")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo, hi):
    """The complement of merged ``busy`` within [lo, hi]."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def leaves(events):
    """Drop container events: those inside which another event starts."""
    evs = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]]


def clock_offset(modules, enqueued, completed) -> float:
    """ns to add to device times: the midpoint of the offsets every run
    allows, each run matched across host and device by ``run_id``."""
    lo, hi = [], []
    for rid, (s, e) in modules.items():
        if rid in enqueued:
            lo.append(enqueued[rid] - s)
        if rid in completed:
            hi.append(completed[rid] - e)
    if not lo:
        return 0.0
    a = max(lo)
    b = min(hi) if hi else a
    return (a + b) / 2 if b >= a else a


def _run_id(ev):
    for k, v in ev.stats:
        if k == "run_id":
            return int(v)
    return None


def planes_of(xspace):
    """-> (host spans [(start, end, name)], {device plane name: lines}),
    device times moved onto the host's clock."""
    host, devices, enqueued, completed = [], {}, {}, {}
    for plane in xspace.planes:
        if plane.name.startswith("/device:") and "host" not in plane.name.lower():
            lines, modules = {}, {}
            for line in plane.lines:
                evs = lines.setdefault(line.name, [])
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    if line.name == MODULES_LINE:
                        rid = _run_id(ev)
                        if rid is not None:
                            modules[rid] = evs[-1][:2]
            devices[plane.name] = (lines, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIXES):
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name))
                    elif ev.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        rid = _run_id(ev)
                        if rid is not None:
                            side = enqueued if ev.name == "DoEnqueueProgram" else completed
                            side.setdefault(rid, ev.start_ns)
    out = {}
    for name, (lines, modules) in devices.items():
        off = clock_offset(modules, enqueued, completed)
        out[name] = {ln: [(s + off, e + off, n) for s, e, n in evs]
                     for ln, evs in lines.items()}
    return host, out


def innermost(spans, t):
    """Name of the shortest host span (other than the window) holding t."""
    best = None
    for s, e, name in spans:
        if name != WINDOW and s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no host span"


def reduce(xspace, *, n_devices: int = 1, kernels: dict | None = None,
           top: int = 10) -> dict:
    """``kernels`` maps a key to a substring of the ops' full HLO text (a
    Pallas kernel's name sits in its custom call); the result gives each
    key's device time and call count in the window."""
    host, devices = planes_of(xspace)
    win = [(s, e) for s, e, n in host if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = win[0]
    window_s = (hi - lo) / 1e9
    used = [name for name in sorted(devices, key=_device_index)
            if devices[name].get(OPS_LINE)][:n_devices]
    if not used:
        raise ValueError("no device plane with XLA ops in the trace")
    busy_total, op_time, mod_time = 0.0, defaultdict(float), defaultdict(float)
    mod_calls, kern = defaultdict(int), {k: [0.0, 0] for k in (kernels or {})}
    by_span = defaultdict(lambda: [0.0, 0])
    all_gaps = []
    for name in used:
        ops = [(s, e, n) for s, e, n in leaves(devices[name][OPS_LINE])
               if e > lo and s < hi]
        busy = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for s, e, full in ops:
            d = min(e, hi) - max(s, lo)
            op_time[short_name(full)] += d
            for key, pat in (kernels or {}).items():
                if pat in full:
                    kern[key][0] += d
                    kern[key][1] += 1
        for s, e, n in devices[name].get(MODULES_LINE, []):
            if e > lo and s < hi:
                mod_time[n] += min(e, hi) - max(s, lo)
                mod_calls[n] += 1
                if name == used[0]:
                    rec = by_span[innermost(host, s)]
                    rec[0] += e - s
                    rec[1] += 1
        if name == used[0]:
            all_gaps = [(e - s, innermost(host, (s + e) / 2))
                        for s, e in gaps(busy, lo, hi)]
    k = len(used)
    busy_s = busy_total / k / 1e9
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    gap_by_span = defaultdict(float)
    for d, n in all_gaps:
        gap_by_span[n] += d / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": used,
        "top_ops": [[n, t / k / 1e9] for n, t in ops_sorted[:top]],
        "op_time_s": {n: t / k / 1e9 for n, t in op_time.items()},
        "modules": {n: {"time_s": t / k / 1e9, "calls": mod_calls[n] // k or 1}
                    for n, t in mod_time.items()},
        "programs_by_span": {n: {"time_s": t / 1e9, "calls": c}
                             for n, (t, c) in by_span.items()},
        "kernels": {key: {"time_s": v[0] / k / 1e9, "calls": v[1] // k}
                    for key, v in kern.items()},
        "top_gaps": [[n, d / 1e9] for d, n in sorted(all_gaps, reverse=True)[:top]],
        "gap_s_by_span": dict(gap_by_span),
        "host_spans": _span_totals(host, lo, hi),
    }


def _span_totals(host, lo, hi):
    tot, cnt = defaultdict(float), defaultdict(int)
    for s, e, n in host:
        if n != WINDOW and e > lo and s < hi:
            tot[n] += (min(e, hi) - max(s, lo)) / 1e9
            cnt[n] += 1
    return {n: {"time_s": tot[n], "calls": cnt[n]} for n in tot}


def _device_index(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 1 << 30


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir, **kw) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), **kw)
