"""Stand-alone times of the decode step's sparse-attention pieces on the
chip, one layer at one geometry: the index scores (the Pallas kernel
against the XLA gather of every slot's table), the selection (the
bisection of `kernels.sparse_attention.select_topk` against
`jax.lax.top_k`) and the attention over the selected keys (the
block-table kernel under the selection's mask against a gather of the
selected rows). PERF.md records what a run of this printed.

    python tools/dsa_standalone.py [--slots 32] [--context 7168] ...

and, with `--prefill`, one layer of a prefill at each of the cell's
buckets over a prompt under `--padding` tokens of left padding (a
quarter of the bucket where none is given; 0 is a full bucket): the
selection alone, for every chunk of queries against for the chunks
that `kernels.sparse_attention.chunk_plan` says need one; the LAST
chunk's index scores and attention over every key block of the bucket
(`whole_range`: what both kernels visited before PR 40) beside the
blocks that hold a real key (`live_blocks`); and the whole layer.

Needs a TPU. Prints one JSON line a piece: seconds of one call on the
device (50 calls inside one program, the median of 5 such programs; 10
and 3 for the prefill pieces).
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu.kernels._common import pallas_interpret  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402


def selected_rows_attention(q, k_pages, v_pages, block_tables, keep, topk,
                            scale):
    """The other exact form: gather the selected keys' K and V rows
    ([B, topk, Hkv, D]) and attend densely over them."""
    b, n_keys = keep.shape
    page = k_pages.shape[1]
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1, dtype=jnp.int32) - 1
    pos = jnp.broadcast_to(jnp.arange(n_keys, dtype=jnp.int32), keep.shape)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    idx = jnp.zeros((b, topk), jnp.int32).at[
        rows, jnp.where(keep, rank, topk)].set(pos, mode="drop")
    n_sel = jnp.sum(keep, axis=1, dtype=jnp.int32)
    pages = jnp.take_along_axis(block_tables, idx // page, axis=1)
    k = k_pages[pages, idx % page]                      # [B, topk, Hkv, D]
    v = v_pages[pages, idx % page]
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, -1, q.shape[-1])
    s = jnp.einsum("bgrd,blgd->bgrl", qg, k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    ok = jnp.arange(topk, dtype=jnp.int32)[None, :] < n_sel[:, None]
    p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), axis=-1)
    out = jnp.einsum("bgrl,blgd->bgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def timed(name, fn, tables, *args, reps=50, calls=5):
    """Seconds of ONE call of `fn(tables, *args)` on the device: `reps`
    calls run inside one program, each reading its block tables through
    a zero carried from the call before it, so that no gather or kernel
    is hoisted out of the loop or dropped; a call of that program is
    timed from the host. (A single dispatch costs the host about half a
    millisecond, more than most of these pieces.)"""
    def many(bt, *rest):
        def one(_, carry):
            out = jax.tree_util.tree_leaves(fn(bt + carry, *rest))[0]
            # a zero the compiler cannot fold: every element of the
            # call's result is needed to know that none is NaN
            total = jnp.sum(out.astype(jnp.float32))
            return jnp.where(jnp.isnan(total), jnp.int32(1), jnp.int32(0))
        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(reps), one,
                                 jnp.int32(0))
    prog = jax.jit(many)
    jax.block_until_ready(prog(tables, *args))
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(tables, *args))
        times.append((time.perf_counter() - t0) / reps)
    print(json.dumps({"piece": name, "median_s": statistics.median(times),
                      "min_s": min(times)}), flush=True)


def chunk_forms(name, zero, qi, w, ki, q, k, v, keep, blocks, scale, tile,
                **kw):
    """A bucket's LAST chunk: its index scores and its attention under
    `keep`, each over every key block of the bucket (`whole_range`:
    what both kernels visited before PR 40) and over the blocks
    `blocks` runs, the chunk's table of `chunk_key_blocks`
    (`live_blocks`)."""
    bq, bk = tile
    j = jnp.arange(ki.shape[1] // bk, dtype=jnp.int32)
    whole = jnp.broadcast_to(jnp.stack([j, jnp.ones_like(j)]), blocks.shape)
    moving = lambda bt: keep & (bt[:, :1, None] >= 0)
    for form, tab in (("whole_range", whole), ("live_blocks", blocks)):
        timed(f"prefill.index_scores.{form}.{name}",
              lambda bt: sa.prefill_index_scores(
                  qi, w + bt[0, 0].astype(jnp.float32), ki, tab),
              zero, **kw)
        timed(f"prefill.attend.{form}.{name}",
              lambda bt: sa._attend_pallas(
                  q, k, v, moving(bt), tab, scale, bq, bk,
                  pallas_interpret()), zero, **kw)


def prefill_pieces(a, rng):
    """`prefill.select` old form beside new and `prefill.layer`, a
    bucket."""
    d, c, bf, f32 = 128, 512, jnp.bfloat16, jnp.float32
    zero = jnp.zeros((1, 1), jnp.int32)
    for s, pad in ((s, pad) for s in a.buckets
                   for pad in (a.padding or [s // 4]) if pad < s):
        arr = lambda *sh: jnp.asarray(rng.normal(size=sh).astype(np.float32),
                                      bf)
        key_valid = jnp.arange(s)[None, :] >= pad
        base = jnp.asarray(rng.normal(size=(1, c, s)), f32)
        starts = jnp.arange(0, s, c, dtype=jnp.int32)
        kpos = jnp.arange(s, dtype=jnp.int32)

        # a chunk's scores move with its position and with the carry
        scores_at = lambda start, bt: base + (start + bt[0, 0]).astype(
            f32) * f32(1e-6)

        def whole_bucket(bt):
            def one(start):
                seen = key_valid[:, None, :] & (
                    kpos[None, None, :]
                    <= (start + jnp.arange(c, dtype=jnp.int32))[None, :, None])
                return jnp.sum(sa.select_topk(scores_at(start, bt), seen,
                                              a.topk), dtype=jnp.int32)
            return jax.lax.map(one, starts)

        def planned(bt):
            def one(at):
                start, kind = at
                seen = key_valid[:, None, :] & (
                    kpos[None, None, :]
                    <= (start + jnp.arange(c, dtype=jnp.int32))[None, :, None])
                # branches in the order PADDING, DENSE, SELECTED
                return jnp.sum(jax.lax.switch(kind, [
                    lambda: jnp.zeros_like(seen), lambda: seen,
                    lambda: sa.select_topk(scores_at(start, bt), seen,
                                           a.topk)]), dtype=jnp.int32)
            return jax.lax.map(
                one, (starts, sa.chunk_plan(key_valid, c, a.topk)))

        kw = dict(reps=10, calls=3)
        name = f"{s}.pad{pad}"
        timed(f"prefill.select.whole_bucket.{name}", whole_bucket, zero, **kw)
        timed(f"prefill.select.planned.{name}", planned, zero, **kw)
        same = jnp.array_equal(whole_bucket(zero), planned(zero))
        print(json.dumps({"bucket": s, "padding": pad,
                          "selections_agree": bool(same)}), flush=True)
        q = arr(1, s, a.heads, d)
        k, v = arr(1, s, a.kv_heads, d), arr(1, s, a.kv_heads, d)
        qi, ki = arr(1, s, a.index_heads, a.index_dim), arr(1, s, a.index_dim)
        w = jnp.asarray(rng.normal(size=(1, s, a.index_heads)), f32)
        tail = lambda x: x[:, s - c:]       # the chunk that sees every key
        seen = key_valid[:, None, :] & (
            kpos[None, None, :] <= (s - c + kpos[:c])[None, :, None])
        blocks = sa.chunk_key_blocks(key_valid, c, a.heads // a.kv_heads)[-1]
        keep = sa.select_topk(sa.prefill_index_scores(
            tail(qi), tail(w), ki, blocks), seen, a.topk)
        chunk_forms(name, zero, tail(qi), tail(w), ki, tail(q),
                    k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), keep,
                    blocks, d ** -0.5,
                    sa.attend_tiles(c, a.heads // a.kv_heads, s), **kw)
        timed(f"prefill.layer.{name}", lambda bt: sa.sparse_prefill_attention(
            q, k, v, qi, w + bt[0, 0].astype(f32), ki, key_valid,
            topk=a.topk, scale=d ** -0.5, chunk=c), zero, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--prefill", action="store_true",
                    help="the prefill pieces, and nothing of the decode step")
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[4096, 8192, 16384])
    ap.add_argument("--padding", type=int, nargs="+",
                    help="with --prefill: tokens of left padding, each at "
                    "every bucket that is longer (a quarter of the bucket "
                    "where none is given)")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--context", type=int, default=7168)
    ap.add_argument("--pages-per-seq", type=int, default=1024)
    ap.add_argument("--pool", type=int, default=16385)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--index-heads", type=int, default=16)
    ap.add_argument("--index-dim", type=int, default=64)
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the stand-alone times need a TPU")
    rng = np.random.default_rng(0)
    if a.prefill:
        print(json.dumps({"device": jax.devices()[0].device_kind,
                          "left_padding": a.padding or "bucket / 4"}),
              flush=True)
        prefill_pieces(a, rng)
        return 0
    page, d, bf = 16, 128, jnp.bfloat16
    b, pps = a.slots, a.pages_per_seq
    arr = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32), bf)
    k_pages, v_pages = arr(a.pool, page, a.kv_heads, d), \
        arr(a.pool, page, a.kv_heads, d)
    index_pages = jnp.pad(arr(a.pool, page, a.index_dim),
                          [(0, 0), (0, 0), (0, 128 - a.index_dim)])
    live = -(-a.context // page)
    tables = np.zeros((b, pps), np.int32)
    tables[:, :live] = 1 + rng.permutation(a.pool - 1)[:b * live].reshape(
        b, live)
    tables = jnp.asarray(tables)
    lens = jnp.full((b,), a.context, jnp.int32)
    q, qi = arr(b, a.heads, d), arr(b, a.index_heads, a.index_dim)
    w = jnp.asarray(rng.normal(size=(b, a.index_heads)), jnp.float32)
    scale = d ** -0.5
    print(json.dumps({"geometry": vars(a),
                      "device": jax.devices()[0].device_kind}), flush=True)

    timed("index_scores.pallas", lambda bt: pa.paged_index_scores(
        qi, w, index_pages, bt, lens), tables)
    timed("index_scores.xla_gather", lambda bt: pa._index_scores_xla(
        pa.index_key_rows(qi, index_pages), w, index_pages, bt, lens), tables)
    scores = pa.paged_index_scores(qi, w, index_pages, tables, lens)
    alive = jnp.arange(scores.shape[1])[None, :] < lens[:, None]
    # the selection reads no table: its scores move with the carry
    timed("select.bisection", lambda bt: sa.select_topk(
        scores + bt[:, :1].astype(jnp.float32) * 0, alive, a.topk), tables)
    timed("select.lax_top_k", lambda bt: jax.lax.top_k(
        scores + bt[:, :1].astype(jnp.float32) * 0, a.topk)[1], tables)
    keep = sa.select_topk(scores, alive, a.topk)
    # what each form makes of the selection (the kernel's bias row, the
    # gather's row indices) is part of its cost: the mask moves with the
    # carry too (a table entry is never negative)
    moving = lambda bt: keep & (bt[:, :1] >= 0)
    timed("attend.masked_block_table_kernel",
          lambda bt: pa._paged_attention_pallas(
              q, k_pages, v_pages, bt, lens, scale, keep=moving(bt)), tables)
    timed("attend.selected_rows_gather",
          lambda bt: selected_rows_attention(
              q, k_pages, v_pages, bt, moving(bt), a.topk, scale), tables)
    timed("attend.dense_block_table_kernel",
          lambda bt: pa._paged_attention_pallas(
              q, k_pages, v_pages, bt, lens, scale), tables)
    timed("decode_layer.score_select_attend",
          lambda bt: pa.paged_sparse_attention(
              q, k_pages, v_pages, index_pages, qi, w, bt, lens, a.topk,
              scale)[0], tables)
    got = pa.paged_sparse_attention(q, k_pages, v_pages, index_pages, qi, w,
                                    tables, lens, a.topk, scale)[0]
    other = selected_rows_attention(q, k_pages, v_pages, tables, keep,
                                    a.topk, scale)
    print(json.dumps({"forms_agree_max_abs_diff": float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - other.astype(jnp.float32))))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
