"""Stand-alone times of the dropless expert layer on the chip, one layer
at a cell's geometry, with its tokens cut into pieces of each size in
`--blocks`: the whole layer (`_moe_in_blocks`: what a prefill program
runs, a `lax.map` over the pieces), the routing alone (router matmul
and rule: the `sort f32[piece, experts]`), and the two grouped matmuls
alone on rows already gathered, under the group sizes the routing gave;
what is left of the layer (the sort by expert, the gathers there and
back, the gated sum, the counts) is printed as `rest_s`. Every time is
for `--tokens` tokens, so the sizes compare. `block_tokens` is printed
beside them: the piece the layer would choose. With `--trace` the whole
layer runs under the profiler instead and its device ops are printed by
name, milliseconds a call (the timed pieces carry the timing loop and
the SiLU fusion; the trace tells the `ragged-dot` kernels from the
gathers). PERF.md records what a run of this printed.

    python tools/moe_standalone.py [--cell ling3f-longdoc-open]
        [--tokens 16384] [--blocks 2048 4096 8192 16384] [--trace]

Needs a TPU. Prints one JSON line a piece (tools/kda_standalone.py's
`timed`: 10 calls inside one program, the median of 5 such programs).
"""
import argparse
import functools
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu  # noqa: E402,F401
from paddle_tpu.incubate.distributed.models.moe import dropless  # noqa: E402
from paddle_tpu.kernels._common import mxu_precision  # noqa: E402
from kda_standalone import timed  # noqa: E402

# one expert layer of each cell that has one (benchmarks/configs/*.json):
# the router's width, the experts this chip holds (the leading ones),
# and the routing rule
CELLS = {
    "granite4h-chat-open": dict(hidden=4096, width=768, experts=72, held=36,
                                top_k=10),
    "keye2-longprompt-open": dict(hidden=2048, width=768, experts=128,
                                  held=16, top_k=8),
    "ling3f-longdoc-open": dict(hidden=2560, width=768, experts=512, held=128,
                                top_k=8, n_group=8, topk_group=4, scale=2.5,
                                bias_std=0.05),
}


def route_of(g, bias):
    if "n_group" not in g:
        return None
    return lambda logits: dropless.group_limited_sigmoid_route(
        logits, bias, g["top_k"], g["n_group"], g["topk_group"], g["scale"])


def routing(x, router_w, bias, *, g, block):
    """The router and its rule over pieces of `block` tokens -> the
    local expert of every assignment [pieces, block x top_k]."""
    local_of = jnp.where(jnp.arange(g["experts"]) < g["held"],
                         jnp.arange(g["experts"]), g["held"]).astype(jnp.int32)
    route = route_of(g, bias) or functools.partial(
        dropless.softmax_topk_route, top_k=g["top_k"])

    def piece(xv):
        logits = jnp.dot(xv, router_w, preferred_element_type=jnp.float32)
        return local_of[route(logits)[1]].reshape(-1)
    return jax.lax.map(piece, x.reshape(-1, block, x.shape[1]))


def grouped(xs, sizes, w_in, w_out):
    """The two grouped matmuls of `_route_block` over pieces of rows
    already sorted by expert: xs [pieces, rows, h], sizes [pieces,
    held]."""
    f = w_out.shape[1]
    prec = mxu_precision(xs, w_in)

    def piece(a):
        up = jax.lax.ragged_dot(a[0], w_in, a[1], precision=prec)
        act = jax.nn.silu(up[:, :f].astype(jnp.float32)) \
            * up[:, f:].astype(jnp.float32)
        return jax.lax.ragged_dot(act.astype(xs.dtype), w_out, a[1],
                                  precision=prec)
    return jax.lax.map(piece, (xs, sizes))


def operands(g, tokens, rng):
    """x, router, choice bias and the two banks at geometry `g`."""
    bf = jnp.bfloat16
    draw = lambda std, *s: jnp.asarray(
        rng.normal(size=s).astype(np.float32) * std)
    x = draw(1.0, tokens, g["hidden"]).astype(bf)
    router_w = draw(0.02, g["hidden"], g["experts"]).astype(bf)
    bias = draw(g.get("bias_std", 0.0), g["experts"])
    # the banks are made on the device: 1.5 GB at cell 5's geometry
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w_in = (jax.random.normal(k1, (g["held"], g["hidden"], 2 * g["width"]),
                              jnp.float32) * 0.02).astype(bf)
    w_out = (jax.random.normal(k2, (g["held"], g["width"], g["hidden"]),
                               jnp.float32) * 0.02).astype(bf)
    return x, router_w, bias, w_in, w_out


def layer_of(g, block):
    held = tuple(range(g["held"]))
    return lambda x_, rw, b, wi, wo: dropless._moe_in_blocks(
        x_, None, rw, wi, wo, held=held, top_k=g["top_k"],
        route=route_of(g, b), block=block)[0]


def traced(cell, g, tokens, blocks, rng, calls=5):
    """The layer's device ops under the profiler, a piece size."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.lib import trace_reduce
    args = operands(g, tokens, rng)
    for block in blocks:
        if tokens % block:
            continue
        fn = jax.jit(layer_of(g, block))
        jax.block_until_ready(fn(*args))
        where = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(where):
                for _ in range(calls):
                    jax.block_until_ready(fn(*args))
            red = trace_reduce.reduce_planes(trace_reduce.load(where))
        finally:
            shutil.rmtree(where, ignore_errors=True)
        ops = sorted(([n, round(v["total_s"] / calls * 1e3, 4),
                       v["calls"] // calls] for n, v in red["ops"].items()),
                     key=lambda o: -o[1])
        print(json.dumps({"cell": cell, "tokens": tokens, "block": block,
                          "busy_ms_a_call": red["busy_s"] / calls * 1e3,
                          "ops_ms_a_call_calls": ops[:16]}), flush=True)


def pieces(cell, g, tokens, blocks, rng):
    x, router_w, bias, w_in, w_out = operands(g, tokens, rng)
    for block in blocks:
        if tokens % block:
            continue
        tag = {"cell": cell, "tokens": tokens, "block": block}
        name = f"{cell}.block{block}."
        loc = jax.jit(functools.partial(routing, g=g, block=block))(
            x, router_w, bias)
        sizes = jax.vmap(lambda l: dropless._count(l, g["held"]))(loc)
        local = np.asarray(sizes.sum(axis=1))
        print(json.dumps({**tag, "local_rows_a_piece_mean": float(local.mean()),
                          "rows_an_expert_mean": float(local.mean() / g["held"]),
                          "rows_an_expert_max": int(np.asarray(sizes).max())}),
              flush=True)
        out = {}
        out["layer_s"] = timed(
            name + "layer", layer_of(g, block),
            x, router_w, bias, w_in, w_out, carried=1)
        out["route_s"] = timed(
            name + "route",
            functools.partial(routing, g=g, block=block),
            x, router_w, bias, carried=1)
        xs = jnp.broadcast_to(x[:block], (g["top_k"], block, g["hidden"])) \
            .reshape(1, block * g["top_k"], g["hidden"])
        xs = jnp.broadcast_to(xs, (tokens // block,) + xs.shape[1:])
        out["grouped_matmuls_s"] = timed(
            name + "grouped_matmuls", grouped,
            xs, sizes, w_in, w_out, carried=1)
        out["rest_s"] = out["layer_s"] - out["route_s"] \
            - out["grouped_matmuls_s"]
        print(json.dumps({**tag, **out}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", nargs="+", default=["ling3f-longdoc-open"],
                    choices=sorted(CELLS))
    ap.add_argument("--tokens", type=int, default=16384,
                    help="tokens every time is for")
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[2048, 4096, 8192, 16384])
    ap.add_argument("--trace", action="store_true",
                    help="the layer's device ops by name, not the times")
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the stand-alone times need a TPU")
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for cell in a.cell:
        g = CELLS[cell]
        print(json.dumps({"cell": cell, "geometry": g, "block_tokens":
                          dropless.block_tokens(
                              a.tokens, g["top_k"], g["experts"],
                              2 * g["hidden"], 2 * g["held"] * 3
                              * g["hidden"] * g["width"])}), flush=True)
        (traced if a.trace else pieces)(
            cell, g, a.tokens, a.blocks, np.random.default_rng(0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
