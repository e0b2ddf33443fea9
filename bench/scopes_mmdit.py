"""Device time under the MMDiT's named scopes (``mmdit``, ``mmdit.embed``,
``mmdit.adaln``, ``mmdit.attn.qkv``, ``mmdit.attn.core``,
``mmdit.attn.out``, ``mmdit.mlp``, ``mmdit.final``; DESIGN.md §13) in a
traced benchmark run.

``bench/scopes.py`` totals only the scopes it lists. This module reads the
same ``.xplane.pb`` with its decoder (``scopes.read_xspace``), device and
host split and window, and gives each op's device time to every
``mmdit``-named scope in its name stack; ``while`` ops are containers and
count nothing. The step counts (FULL and COND steps run) come from
``scopes.for_run``, on the shared step scopes.
"""

from __future__ import annotations

import sys
from collections import defaultdict

from bench import flops_mmdit as FM
from bench import scopes as S
from bench import trace_reduce as TR

PREFIX = "mmdit"
ATTN_CORE = "mmdit.attn.core"


def scope_path(tf_op: str | None) -> list[str]:
    """The ``mmdit`` scopes in a ``tf_op`` name stack, outermost first."""
    if not tf_op:
        return []
    stack = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return [c for c in stack.split("/") if c == PREFIX or c.startswith(PREFIX + ".")]


def reduce(planes, *, n_devices: int = 1) -> dict:
    """{"window_s", "scopes": {scope: device s}} over the ``bench.window``
    span, averaged over the devices used."""
    host, devices = S._host_and_devices(planes)
    win = [(s, e) for s, e, n in host if n == TR.WINDOW]
    if not win:
        raise ValueError(f"no {TR.WINDOW!r} span in the trace")
    lo, hi = win[0]
    used = [n for n in sorted(devices, key=TR._device_index)
            if devices[n][1]][:n_devices]
    if not used:
        raise ValueError("no device plane with XLA ops in the trace")
    time_s = defaultdict(float)
    for dev in used:
        plane, ops, _ = devices[dev]
        paths = {}
        for s, e, mid in ops:
            if e <= lo or s >= hi or S.CONTAINER.search(plane.event_name(mid)):
                continue
            if mid not in paths:
                paths[mid] = scope_path(plane.meta.get(mid, ("", {}))[1].get("tf_op"))
            for sc in set(paths[mid]):
                time_s[sc] += min(e, hi) - max(s, lo)
    k = len(used)
    return {"window_s": (hi - lo) / 1e9,
            "scopes": {sc: t / k / 1e9 for sc, t in sorted(time_s.items())}}


_CACHE: dict = {}


def reduce_dir(trace_dir, *, n_devices: int = 1) -> dict:
    """``reduce`` of the newest trace under ``trace_dir``, once per file;
    the first call prints the per-scope totals on standard error."""
    path = TR.find_xplane(trace_dir)
    if path not in _CACHE:
        _CACHE[path] = r = reduce(S.read_xspace(path), n_devices=n_devices)
        for sc, t in sorted(r["scopes"].items(), key=lambda kv: -kv[1]):
            print(f"scope {sc}: {t:.6f} s", file=sys.stderr)
    return _CACHE[path]


def for_run(ctx: dict) -> dict | None:
    """The reduction of a traced benchmark run; ``None`` where the trace
    names no ``mmdit`` scope (a program without the MMDiT) or cannot be
    read (the error goes to standard error)."""
    from bench.run import TRACE_DIR
    try:
        r = reduce_dir(TRACE_DIR, n_devices=ctx["chips"])
    except Exception as e:  # a reader returns nothing rather than raise
        print(f"scopes_mmdit: no reduction: {e!r}", file=sys.stderr)
        return None
    return r if PREFIX in r["scopes"] else None


def per_image_ms(ctx: dict, scope: str) -> float | None:
    """Device time under ``scope`` over the images finished, in ms."""
    r, images = for_run(ctx), ctx["counters"].get("images")
    if r is None or not images or scope not in r["scopes"]:
        return None
    return 1e3 * r["scopes"][scope] / images


def joint_attn_roofline(ctx: dict) -> float | None:
    """The joint attention kernel's share of its roofline, in %: the least
    time the chip could take for the attentions run in the window (the
    larger of operations over the bf16 peak and bytes over HBM bandwidth,
    ``bench/flops_mmdit.py``, at the real token count) over the device
    time under ``mmdit.attn.core``. Attentions run: (2 x FULL + COND
    steps) x batch x blocks."""
    r, steps = for_run(ctx), S.for_run(ctx)
    if r is None or steps is None or not r["scopes"].get(ATTN_CORE):
        return None
    full, cond = (steps["steps"].get(sc) for sc in S.STEP_SCOPES)
    if full is None or cond is None:
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    calls = (2 * full + cond) * int(ctx["traffic"]["batch"]) * cfg["num_layers"]
    least = max(calls * FM.joint_attn_flops(cfg) / peaks["flops_bf16"],
                calls * FM.joint_attn_bytes(cfg) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / r["scopes"][ATTN_CORE]
