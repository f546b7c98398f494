"""Stand-alone times, on the chip, of the pieces of latent attention
under an indexer at GLM-5's shapes (models/glm_moe_dsa.py), one layer:

- a prefill chunk's attention under its selection in the two exact
  forms: DECOMPRESSED (64 KV heads at `rep` 1, keys and values 256
  wide, formed from the latent once a layer: `decompress`) against
  ABSORBED (one 576-wide row on 640 lanes for 64 heads as the rows of
  one matmul, key and value both), each through the masked flash kernel
  of `kernels.sparse_attention`, for the LAST chunk of 512 queries of a
  bucket (the one that sees every key); with the chunk's index scores
  at 32 heads of 128 and its selection beside them. Under `--padding`
  tokens of left padding the kept form and the index scores are timed
  over every key block of the bucket (`whole_range`: what the kernels
  visited before PR 40) beside the blocks that hold a real key
  (`live_blocks`);
- a decode step's pieces at the cell's geometry: the index scores, the
  selection, and the latent kernel under the selection's mask against
  the same kernel over every live row and against a gather of the
  selected rows.

    python tools/mla_dsa_standalone.py [--buckets 8192 16384] ...

Needs a TPU. Prints one JSON line a piece (`tools/dsa_standalone.py`
`timed`: seconds of one call on the device). PERF.md records what a run
of this printed, and which form lost.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu.kernels._common import pallas_interpret  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sa  # noqa: E402
from dsa_standalone import chunk_forms, timed  # noqa: E402

BF, F32 = jnp.bfloat16, jnp.float32


def selected_rows_attention(q, pages, block_tables, keep, topk, scale):
    """The other exact decode form: gather the selected rows ([B, topk,
    lanes]) and attend densely over them."""
    b, n_keys = keep.shape
    page = pages.shape[1]
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1, dtype=jnp.int32) - 1
    pos = jnp.broadcast_to(jnp.arange(n_keys, dtype=jnp.int32), keep.shape)
    idx = jnp.zeros((b, topk), jnp.int32).at[
        jnp.arange(b, dtype=jnp.int32)[:, None],
        jnp.where(keep, rank, topk)].set(pos, mode="drop")
    n_sel = jnp.sum(keep, axis=1, dtype=jnp.int32)
    rows = pages[jnp.take_along_axis(block_tables, idx // page, axis=1),
                 idx % page]                            # [B, topk, lanes]
    s = jnp.einsum("bhd,bld->bhl", q, rows,
                   preferred_element_type=F32) * np.float32(scale)
    ok = jnp.arange(topk, dtype=jnp.int32)[None, :] < n_sel[:, None]
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bhl,bld->bhd", p.astype(rows.dtype), rows,
                      preferred_element_type=F32).astype(q.dtype)


def attempt(name, fn, *args, **kw):
    try:
        timed(name, fn, *args, **kw)
    except Exception as e:      # the compiler's refusal is the finding
        print(json.dumps({"piece": name, "refused":
                          f"{type(e).__name__}: {str(e)[:300]}"}), flush=True)


def prefill_pieces(a, rng):
    h, dk, lanes, c = a.heads, 256, 640, 512
    arr = lambda *sh: jnp.asarray(rng.normal(size=sh).astype(np.float32), BF)
    zero = jnp.zeros((1, 1), jnp.int32)
    kw = dict(reps=10, calls=3)
    for s, pad in ((s, pad) for s in a.buckets for pad in a.padding
                   if pad < s):
        scale = dk ** -0.5
        name = f"{s}.pad{pad}"
        q, k, v = arr(1, c, h, dk), arr(1, h, s, dk), arr(1, h, s, dk)
        qi, ki = arr(1, c, a.index_heads, a.index_dim), arr(1, s, a.index_dim)
        w = jnp.asarray(rng.normal(size=(1, c, a.index_heads)), F32)
        key_valid = jnp.arange(s)[None, :] >= pad
        blocks = sa.chunk_key_blocks(key_valid, c, 1)[-1]
        seen = key_valid[:, None, :] & (
            jnp.arange(s)[None, None, :]
            <= (s - c + jnp.arange(c))[None, :, None])
        scores = sa.prefill_index_scores(qi, w, ki, blocks)
        keep = sa.select_topk(scores, seen, a.topk)
        moving = lambda bt: keep & (bt[:, :1, None] >= 0)
        attempt(f"prefill.select.{name}", lambda bt: sa.select_topk(
            scores + bt[0, 0].astype(F32), seen, a.topk), zero, **kw)
        # the kept form (the tile `selected_attention` derives at `rep`
        # 1), over the whole range of key blocks and over the live ones
        chunk_forms(name, zero, qi, w, ki, q, k, v, keep, blocks, scale,
                    sa.attend_tiles(c, 1, s), **kw)
        if pad:     # the forms that lost are timed on a full bucket
            continue
        # every block of the full bucket runs: a table of `tiles` rows
        whole = lambda bq: jnp.broadcast_to(
            blocks[:, :1], (1, c // bq) + blocks.shape[2:])
        for bq in (128, 256):
            attempt(f"prefill.attend.decompressed.bq{bq}.{s}",
                    lambda bt: sa._attend_pallas(
                        q, k, v, moving(bt), whole(bq), scale, bq, 512,
                        pallas_interpret()), zero, **kw)
        q_abs, rows = arr(1, c, h, lanes), arr(1, 1, s, lanes)
        latent, w_kv = arr(1, s, 512), arr(512, h, 448)
        attempt(f"prefill.decompress_k_and_v.{s}", lambda bt: jnp.einsum(
            "nsc,chd->nhsd", latent + bt[0, 0].astype(BF), w_kv), zero, **kw)
        for bq in (16, 32):
            attempt(f"prefill.attend.absorbed.bq{bq}.{s}",
                    lambda bt: sa._attend_pallas(
                        q_abs, rows, rows, moving(bt), whole(bq), scale, bq,
                        512, pallas_interpret()), zero, **kw)


def decode_pieces(a, rng):
    page, lanes, b, pps = 16, 640, a.slots, a.pages_per_seq
    arr = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32), BF)
    pages = arr(a.pool, page, lanes)
    index_pages = arr(a.pool, page, 128)
    live = -(-a.context // page)
    tables = np.zeros((b, pps), np.int32)
    tables[:, :live] = 1 + rng.permutation(a.pool - 1)[:b * live].reshape(
        b, live)
    tables = jnp.asarray(tables)
    lens = jnp.full((b,), a.context, jnp.int32)
    q, qi = arr(b, a.heads, lanes), arr(b, a.index_heads, a.index_dim)
    w = jnp.asarray(rng.normal(size=(b, a.index_heads)), F32)
    scale = 256 ** -0.5
    timed("index_scores.pallas", lambda bt: pa.paged_index_scores(
        qi, w, index_pages, bt, lens), tables)
    scores = pa.paged_index_scores(qi, w, index_pages, tables, lens)
    alive = jnp.arange(scores.shape[1])[None, :] < lens[:, None]
    timed("select.bisection", lambda bt: sa.select_topk(
        scores + bt[:, :1].astype(F32) * 0, alive, a.topk), tables)
    keep = sa.select_topk(scores, alive, a.topk)
    moving = lambda bt: keep & (bt[:, :1] >= 0)
    timed("attend.masked_latent_kernel",
          lambda bt: la._latent_attention_pallas(
              q, pages, bt, lens, scale, pallas_interpret(),
              keep=moving(bt)), tables)
    timed("attend.dense_latent_kernel",
          lambda bt: la._latent_attention_pallas(
              q, pages, bt, lens, scale, pallas_interpret()), tables)
    attempt("attend.selected_rows_gather", lambda bt: selected_rows_attention(
        q, pages, bt, moving(bt), a.topk, scale), tables)
    timed("decode_layer.score_select_attend",
          lambda bt: la.paged_sparse_latent_attention(
              q, pages, index_pages, qi, w, bt, lens, a.topk, scale)[0],
          tables)
    got = la.paged_sparse_latent_attention(
        q, pages, index_pages, qi, w, tables, lens, a.topk, scale)[0]
    other = selected_rows_attention(q, pages, tables, keep, a.topk, scale)
    print(json.dumps({"forms_agree_max_abs_diff": float(jnp.max(jnp.abs(
        got.astype(F32) - other.astype(F32))))}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--buckets", type=int, nargs="+", default=[8192, 16384])
    ap.add_argument("--prefill-only", action="store_true")
    ap.add_argument("--padding", type=int, nargs="+", default=[0],
                    help="tokens of left padding of the prefill's prompt, "
                    "each at every bucket that is longer; the forms that "
                    "lost are timed at 0 only")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--context", type=int, default=7168)
    ap.add_argument("--pages-per-seq", type=int, default=1024)
    ap.add_argument("--pool", type=int, default=16385)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--index-heads", type=int, default=32)
    ap.add_argument("--index-dim", type=int, default=128)
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the stand-alone times need a TPU")
    rng = np.random.default_rng(0)
    print(json.dumps({"geometry": vars(a),
                      "device": jax.devices()[0].device_kind}), flush=True)
    if not a.prefill_only:
        decode_pieces(a, rng)
    prefill_pieces(a, rng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
