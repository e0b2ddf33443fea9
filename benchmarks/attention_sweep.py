"""Time the flash kernel's bodies one call at a time on a TPU: the sweep
behind the joint attention's blocks and body (PERF.md §6).

Each body spec is timed best of 3 x 5 calls at two row counts and checked
against a ``highest``-precision einsum at one row (max |diff|):

  joint   one MMDiT ``joint_attention`` call (concat, pad, kernel, slice)
          at SD3-Medium's widths, 4429 tokens padded to one 4480-key
          block; 4 and 2 rows
  unet32  the UNet's 32x32 self-attention kernel call (1024 positions, 8
          heads of 80, blocks (1024, 1024)); 8 and 4 rows

Body specs:

  online:BQ          the online softmax over the one kv block
  chunks:BQ:C        the online softmax over chunks of C keys inside the
                     block, unrolled, the running state carried as values
                     (an inner kv tiling; not in the program)
  one-pass:BQ:ROWS   the program's one-pass body, ROWS rows a group

Usage: PYTHONPATH=src python -m benchmarks.attention_sweep \
           [--case joint] [--out sweep.json] [SPEC ...]
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import flash_attention as FA
from repro.models import mmdit as M

DEFAULT = ["online:448", "one-pass:1120:224", "chunks:560:2240"]


def _chunks_kernel(c, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, causal, window, bq, bk, nk, kv_len):
    """``FA._kernel`` for non-causal calls, walking its kv block in
    unrolled chunks of ``c`` keys; only the chunk holding key kv_len - 1
    is masked and the chunks after it are skipped."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, FA.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def sweep(last_key):
        q = q_ref[...]
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        for c0 in range(0, bk, c):
            if last_key is not None and c0 > last_key:
                break
            w = min(c, bk - c0)
            k, v = k_ref[c0:c0 + w, :], v_ref[c0:c0 + w, :]
            s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if last_key is not None and last_key < c0 + w:
                kpos = j * bk + c0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)
                s = jnp.where(kpos < kv_len, s, FA.NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = m_new
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

    if kv_len is None:
        sweep(None)
    else:
        last = (kv_len - 1) // bk
        pl.when(j < last)(lambda: sweep(None))
        pl.when(j == last)(lambda: sweep((kv_len - 1) % bk))

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@contextlib.contextmanager
def _body(spec: str, rep: int, bk: int):
    """Trace the flash kernel with the body ``spec`` names; yields bq."""
    kind, *nums = spec.split(":")
    bq, *rest = map(int, nums)
    saved = FA._one_pass, FA._kernel, FA.SCORE_TILE_BYTES
    if kind == "online":
        FA._one_pass = lambda *a: False
    elif kind == "chunks":
        FA._one_pass = lambda *a: False
        FA._kernel = functools.partial(_chunks_kernel, rest[0])
    elif kind == "one-pass":
        FA.SCORE_TILE_BYTES = 4 * rep * bk * rest[0]
        assert FA._row_group(bq, rep, bk) == rest[0], spec
    else:
        raise ValueError(spec)
    try:
        yield bq
    finally:
        FA._one_pass, FA._kernel, FA.SCORE_TILE_BYTES = saved


def _joint(spec):
    n, m, d, heads = 4096, 333, 1536, 24

    def call(qx, qy):
        with _body(spec, 1, 4480) as bq:
            orig = M._joint_blocks
            M._joint_blocks = lambda s: (bq, 4480)
            try:
                return M.joint_attention(qx, qy, heads)
            finally:
                M._joint_blocks = orig

    def inputs(rows):
        key = jax.random.PRNGKey(rows)
        return (jax.random.normal(key, (rows, n, 3 * d), jnp.float32),
                jax.random.normal(jax.random.fold_in(key, 1), (rows, m, 3 * d),
                                  jnp.float32))

    def ref(qx, qy):
        orig = M._joint_blocks
        M._joint_blocks = lambda s: None
        try:
            return M.joint_attention(qx, qy, heads)
        finally:
            M._joint_blocks = orig

    return call, inputs, ref, (4, 2)


def _unet32(spec):
    n, heads, hd = 1024, 8, 80

    def call(q, k, v):
        with _body(spec, 1, n) as bq:
            return FA.flash_attention_pallas(
                q, k, v, causal=False, bq=bq, bk=n, scale=1.0,
                out_dtype=jnp.float32, interpret=False)

    def inputs(rows):
        key = jax.random.PRNGKey(rows)
        return tuple((jax.random.normal(jax.random.fold_in(key, i),
                                        (rows, n, heads, hd), jnp.float32)
                      * (hd ** -0.25 if i < 2 else 1.0)).astype(jnp.bfloat16)
                     for i in range(3))

    def ref(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        w = jax.nn.softmax(jnp.einsum("bqhk,bshk->bhqs", q, k), axis=-1)
        return jnp.einsum("bhqs,bshk->bqhk", w, v)

    return call, inputs, ref, (8, 4)


CASES = {"joint": _joint, "unet32": _unet32}


def _best_ms(f, args) -> float:
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(5):
            o = f(*args)
        jax.block_until_ready(o)
        best = min(best, (time.perf_counter() - t) / 5)
    return 1e3 * best


def _max_diff(a, b) -> float:
    return max(float(jnp.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("specs", nargs="*", default=DEFAULT)
    ap.add_argument("--case", choices=sorted(CASES), default="joint")
    ap.add_argument("--out", help="write the records to this JSON file too")
    args = ap.parse_args()
    _, inputs, ref, row_counts = CASES[args.case](args.specs[0])
    x = {r: inputs(r) for r in (*row_counts, 1)}
    with jax.default_matmul_precision("highest"):
        expect = jax.block_until_ready(jax.jit(ref)(*x[1]))
    records = []
    print("device", jax.devices()[0].device_kind, flush=True)
    for spec in args.specs:
        rec = {"case": args.case, "spec": spec}
        try:
            f = jax.jit(CASES[args.case](spec)[0])
            for r in row_counts:
                rec[f"ms{r}"] = _best_ms(f, x[r])
            rec["err"] = _max_diff(f(*x[1]), expect)
        except Exception as e:  # a block the compiler refuses (VMEM)
            rec["error"] = str(e).splitlines()[0][:160]
        records.append(rec)
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as fh:
                json.dump({"device": jax.devices()[0].device_kind,
                           "records": records}, fh, indent=1)


if __name__ == "__main__":
    main()
