"""Stand-alone times of the prefill's chunked delta rule on the chip, one
layer at the cell's geometry (`ling3f-longdoc-open`: a segment of 1024
tokens, 32 heads of 128, chunks of 64 in sub-chunks of 16), for two
prompts a program and for one: the whole of `kda_chunked`, the chunk's
inverse plus `T @ rhs` alone (`unit_lower_inverse`, what the function
runs), and `jax.lax.linalg.triangular_solve` on the same systems beside
it: JAX's own function, the form the function had before PR 36, so that
the old form stays measurable without living in the tree. PERF.md
records what a run of this printed.

    python tools/kda_standalone.py [--rows 2 1] [--tokens 1024] ...

Needs a TPU. Prints one JSON line a piece: seconds of one call on the
device (10 calls inside one program, the median of 5 such programs).
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
from paddle_tpu.kernels.kda import kda_chunked, unit_lower_inverse  # noqa: E402

F32 = jnp.float32


def timed(name, fn, *args, reps=10, calls=5, carried=None):
    """Seconds of ONE call of `fn(*args)` on the device: `reps` calls
    run inside one program, each reading EVERY argument through a zero
    carried from the call before it, so that nothing is hoisted out of
    the loop or dropped (the chunk's systems hold no q: a zero on q
    alone leaves them outside the loop); a call of that program is timed
    from the host (tools/dsa_standalone.py). `carried` = c hands the
    zero to the first c arguments alone, where the others are weights
    that every piece of the work meets an activation with (adding to a
    bank of 1.5 GB would time the copy)."""
    def many(*args):
        c = len(args) if carried is None else carried

        def one(_, carry):
            out = jax.tree_util.tree_leaves(
                fn(*(a + carry.astype(a.dtype) if i < c else a
                     for i, a in enumerate(args))))[0]
            total = jnp.sum(out.astype(F32))
            return jnp.where(jnp.isnan(total), F32(1), F32(0))
        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(reps), one, F32(0))
    prog = jax.jit(many)
    t0 = time.perf_counter()
    jax.block_until_ready(prog(*args))
    first_call = time.perf_counter() - t0
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(*args))
        times.append((time.perf_counter() - t0) / reps)
    print(json.dumps({"piece": name, "median_s": statistics.median(times),
                      "min_s": min(times), "first_call_s": first_call}),
          flush=True)
    return statistics.median(times)


def block_inverse(strict, rhs, sub):
    return jnp.einsum("bhnti,bhnix->bhntx", unit_lower_inverse(strict, sub),
                      rhs, preferred_element_type=F32)


def xla_solve(strict, rhs):
    return jax.lax.linalg.triangular_solve(
        jnp.eye(strict.shape[-1], dtype=F32) + strict, rhs, left_side=True,
        lower=True, unit_diagonal=True)


def pieces(a, b, rng):
    l, h, d, c = a.tokens, a.heads, a.head_dim, a.chunk
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    draw = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k, v = unit(draw(b, l, h, d)) * d ** -0.5, unit(draw(b, l, h, d)), \
        draw(b, l, h, d)
    g = (a.lower * rng.uniform(size=(b, l, h, d))).astype(np.float32)
    beta = rng.uniform(size=(b, l, h)).astype(np.float32)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    timed(f"kda_chunked.rows{b}", lambda q_, *rest: kda_chunked(
        q_, *rest, chunk=c, sub=a.sub), bf(q), bf(k), bf(v), jnp.asarray(g),
        jnp.asarray(beta))
    # the same systems for both forms: diag(beta) tril(K K^T, -1) of
    # every chunk (no decay: the largest entries), [b, h, chunks, c, c],
    # and 2 x head_dim right-hand sides a system
    kc = k.reshape(b, l // c, c, h, d).transpose(0, 3, 1, 2, 4)
    bc = beta.reshape(b, l // c, c, h).transpose(0, 3, 1, 2)
    strict = jnp.asarray(bc[..., None] * np.tril(
        np.einsum("bhntd,bhnid->bhnti", kc, kc), -1))
    rhs = jnp.asarray(draw(b, h, l // c, c, 2 * d))
    timed(f"block_inverse_and_apply.rows{b}",
          lambda n, r: block_inverse(n, r, a.sub), strict, rhs)
    timed(f"xla_triangular_solve.rows{b}", xla_solve, strict, rhs)
    want = xla_solve(strict, rhs)
    got = jax.jit(lambda n, r: block_inverse(n, r, a.sub))(strict, rhs)
    print(json.dumps({"rows": b, "forms_agree_max_abs_diff_over_largest":
                      float(jnp.abs(got - want).max() / jnp.abs(want).max())}),
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[2, 1],
                    help="prompts a prefill program")
    ap.add_argument("--tokens", type=int, default=1024, help="a segment")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--sub", type=int, default=16)
    ap.add_argument("--lower", type=float, default=-5.0)
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the stand-alone times need a TPU")
    print(json.dumps({"geometry": vars(a),
                      "device": jax.devices()[0].device_kind}), flush=True)
    rng = np.random.default_rng(0)
    for b in a.rows:
        pieces(a, b, rng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
