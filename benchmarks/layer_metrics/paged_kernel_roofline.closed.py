"""Share of its roofline that the decode step's paged-attention kernel
reaches in the closed cell. `ragged_paged_roofline`'s arithmetic, which
holds for any paged decode kernel (the bytes of
`benchmarks/kernels/ragged_paged.py` are the least one can move: K and V
of the live pages, the queries in, the outputs back, at the window's mean
active slots and mean cached length; over the median device time of the
decode step's `[slots, heads, head_dim]` custom call), under the closed
cell's end-to-end metric. A program whose decode step holds no such
kernel (GQA on the XLA block-table path) gives nothing."""
from benchmarks.lib import harness

NAME, UNIT = "paged_kernel_roofline.closed", "%"
LAYER, MOVES = "paged kernels", "serve_tokens_per_s"


def read(record, trace):
    if not record.get("root"):
        return None
    return harness.load_module(record["root"], "layer_metrics",
                               "ragged_paged_roofline").read(record, trace)
