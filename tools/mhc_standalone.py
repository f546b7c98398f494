"""Stand-alone, on the chip, of the residual-stream kernels at Xing4.0's
shapes (kernels/hyper_connections.py; four streams of 3584 in bfloat16):

- `mhc_pre` and `mhc_post` against their XLA forms on the chip's own
  arithmetic (the largest difference of the maps, of what a sublayer
  reads and of the mixed streams), and the seconds of one call of each
  form at a prompt's row counts and a decode step's;
- with `--model`, where the program's bfloat16 leaves the float32
  reference layer by layer: a model cut to `--layers` layers run eagerly
  on one prompt, the relative distance of its streams from
  `benchmarks/reference/xing_moe.py`'s after every layer, with the
  kernels and with their XLA forms, and the last position's logits.

    python tools/mhc_standalone.py [--rows 32 2048 8192] [--model 2048]

Needs a TPU. Prints one JSON line a piece (`tools/dsa_standalone.py`
`timed`: seconds of one call on the device). PERF.md records what a run
of this printed.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.kernels import hyper_connections as hc  # noqa: E402
from dsa_standalone import timed  # noqa: E402

BF, F32 = jnp.bfloat16, jnp.float32
N, C = 4, 3584
MAPS = dict(n=N, iters=20, eps=1e-6, hc_eps=1e-6, clamp=(-30.0, 30.0))


def case(rows, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (rows, N * C), F32).astype(BF)
    phi = (jax.random.normal(k[1], (N * C, 24), F32)
           * (N * C) ** -0.5).astype(BF)
    b = 0.5 * jax.random.normal(k[2], (24,), F32)
    b = b.at[8:].add(2.0 * jnp.eye(N, dtype=F32).reshape(-1))
    f = jax.random.normal(k[3], (rows, C), F32).astype(BF)
    return x, phi, jnp.ones((3,), F32), b, f


def kernels(rows):
    x, phi, a, b, f = case(rows)
    pre_k = jax.jit(lambda *v: hc._mhc_pre_pallas(*v, *MAPS.values(), False))
    pre_x = jax.jit(lambda *v: hc._mhc_pre_xla(*v, **MAPS))
    post_k = jax.jit(lambda *v: hc._mhc_post_pallas(*v, N, False))
    post_x = jax.jit(lambda *v: hc._mhc_post_xla(*v, N))
    (u0, c0), (u1, c1) = pre_x(x, phi, a, b), pre_k(x, phi, a, b)
    y0, y1 = post_x(x, f, c0), post_k(x, f, c0)
    far = lambda p, q: float(jnp.max(jnp.abs(p.astype(F32) - q.astype(F32))))
    print(json.dumps({"piece": f"kernel_against_xla.{rows}",
                      "coef": far(c0, c1), "u": far(u0, u1),
                      "streams": far(y0, y1),
                      "u_largest": float(jnp.max(jnp.abs(u0.astype(F32)))),
                      "finite": bool(jnp.isfinite(c1).all()
                                     & jnp.isfinite(y1.astype(F32)).all())}),
          flush=True)
    zero = jnp.zeros((), jnp.int32)
    moved = lambda z: (x + z.astype(BF), f + z.astype(BF))
    for name, fn in (
            ("mhc_pre.kernel", lambda z: pre_k(moved(z)[0], phi, a, b)),
            ("mhc_pre.xla", lambda z: pre_x(moved(z)[0], phi, a, b)),
            ("mhc_post.kernel", lambda z: post_k(*moved(z), c0)),
            ("mhc_post.xla", lambda z: post_x(*moved(z), c0))):
        timed(f"{name}.{rows}", fn, zero)


def model(tokens, layers, seed):
    from benchmarks.lib import harness
    cfg = dict(harness.find_cell(ROOT, "xing4-code-open")["cfg"],
               num_hidden_layers=layers)
    ref = harness.load_module(ROOT, "reference", "xing_moe")
    built = harness.load_module(ROOT, "models", "xing_moe").build(cfg,
                                                                  seed)[0]
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (tokens,),
                                        2, cfg["vocab_size"]), np.int32)
    want = [np.asarray(ref.streams_after(cfg, seed, ids, layers=k + 1))[
        :tokens].reshape(tokens, -1) for k in range(layers)]
    logits = ref.logits_at(cfg, seed, ids, [tokens - 1])[0]
    use = hc._use_pallas
    for route in ("kernel", "xla"):
        hc._use_pallas = use if route == "kernel" else (lambda: False)
        seen = []
        keep = [layer.forward for layer in built.model.layers]
        for layer, fwd in zip(built.model.layers, keep):
            def spy(*a, _fwd=fwd, **kw):
                out = _fwd(*a, **kw)
                seen.append(np.asarray(out[0]._value.astype(F32)))
                return out
            layer.forward = spy
        try:
            with paddle.no_grad():
                got = np.asarray(built(paddle.to_tensor(ids[None]))._value)[
                    0, -1]
        finally:
            for layer, fwd in zip(built.model.layers, keep):
                layer.forward = fwd
            hc._use_pallas = use
        rel = [float(np.linalg.norm(s - w) / np.linalg.norm(w))
               for s, w in zip(seen, want)]
        print(json.dumps({
            "piece": f"model.{route}.{tokens}", "layers": layers,
            "streams_off_by_layer": rel,
            "logits_off": float(np.linalg.norm(got - logits)
                                / np.linalg.norm(logits)),
            "logits_std": float(logits.std()),
            "gap": float(logits.max() - logits[int(got.argmax())])}),
            flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, nargs="*", default=[32, 2048, 8192])
    ap.add_argument("--model", type=int, default=0,
                    help="tokens of the one prompt; 0: kernels only")
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--seed", type=int, default=3000004501)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the stand-alone times need a TPU")
    for rows in args.rows:
        kernels(rows)
    if args.model:
        model(args.model, args.layers, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
