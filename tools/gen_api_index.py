"""Generate docs/API_SURFACE.md: the full public name inventory by
namespace, as a checkable artifact of COVERAGE.md's surface claim.

    JAX_PLATFORMS=cpu python tools/gen_api_index.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def names(mod):
    """Public names, excluding module-import leaks (advisor r4: `jax`,
    `np`, `functools`, `annotations`, ... are implementation imports,
    not API surface — only paddle_tpu's own submodules count)."""
    import types
    out = []
    for n in dir(mod):
        if n.startswith("_"):
            continue
        try:
            v = getattr(mod, n)
        except Exception:
            continue
        if isinstance(v, types.ModuleType):
            name = getattr(v, "__name__", "")
            if not (name == "paddle_tpu" or name.startswith("paddle_tpu.")):
                continue
        if type(v).__name__ == "_Feature":   # `annotations` leak
            continue
        out.append(n)
    return sorted(out)


def main():
    import paddle_tpu as paddle

    sections = {}

    def add(title, mod):
        sections[title] = names(mod)

    add("paddle", paddle)
    add("paddle.Tensor (methods/properties)", paddle.to_tensor([1.0]))
    add("paddle.nn", paddle.nn)
    add("paddle.nn.functional", paddle.nn.functional)
    add("paddle.nn.initializer", paddle.nn.initializer)
    add("paddle.nn.utils", paddle.nn.utils)
    add("paddle.optimizer", paddle.optimizer)
    add("paddle.optimizer.lr", paddle.optimizer.lr)
    add("paddle.regularizer", paddle.regularizer)
    add("paddle.autograd", paddle.autograd)
    add("paddle.amp", paddle.amp)
    add("paddle.io", paddle.io)
    add("paddle.jit", paddle.jit)
    add("paddle.static", paddle.static)
    add("paddle.static.nn", paddle.static.nn)
    add("paddle.linalg", paddle.linalg)
    add("paddle.fft", paddle.fft)
    add("paddle.signal", paddle.signal)
    add("paddle.sparse", paddle.sparse)
    add("paddle.sparse.nn", paddle.sparse.nn)
    add("paddle.distribution", paddle.distribution)
    add("paddle.distributed", paddle.distributed)
    add("paddle.distributed.fleet", paddle.distributed.fleet)
    add("paddle.vision", paddle.vision)
    add("paddle.vision.models", paddle.vision.models)
    add("paddle.vision.transforms", paddle.vision.transforms)
    add("paddle.vision.datasets", paddle.vision.datasets)
    add("paddle.vision.ops", paddle.vision.ops)
    add("paddle.metric", paddle.metric)
    add("paddle.callbacks", paddle.callbacks)
    add("paddle.audio", paddle.audio)
    add("paddle.text", paddle.text)
    add("paddle.geometric", paddle.geometric)
    add("paddle.incubate", paddle.incubate)
    add("paddle.incubate.nn", paddle.incubate.nn)
    add("paddle.inference", paddle.inference)
    add("paddle.onnx", paddle.onnx)
    add("paddle.profiler", paddle.profiler)
    add("paddle.device", paddle.device)
    add("paddle.device.cuda", paddle.device.cuda)
    add("paddle.multiprocessing", paddle.multiprocessing)
    add("paddle.utils", paddle.utils)
    add("paddle.hub", paddle.hub)
    add("paddle.generation", paddle.generation)
    add("paddle.diffusion", paddle.diffusion)
    add("paddle.quantization", __import__("paddle_tpu.quantization",
                                          fromlist=["x"]))

    total = sum(len(v) for v in sections.values())
    out = ["# API surface inventory (generated)",
           "",
           f"Generated by `tools/gen_api_index.py` — "
           f"**{total} public names** across {len(sections)} namespaces. "
           "Regenerate after surface changes; "
           "`tests/test_api_surface.py` gates the required subset.",
           ""]
    for title, nn in sections.items():
        out.append(f"## {title} ({len(nn)})")
        out.append("")
        out.append(", ".join(f"`{n}`" for n in nn))
        out.append("")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(os.path.join(repo, "docs"), exist_ok=True)
    path = os.path.join(repo, "docs", "API_SURFACE.md")
    with open(path, "w") as f:
        f.write("\n".join(out))
    print(f"wrote {path}: {total} names / {len(sections)} namespaces")
    return 0


if __name__ == "__main__":
    sys.exit(main())
