"""The main path's kernels, at Llama-2-7B widths, through the installed
TPU compiler for a described ``v5e:2x2`` with no chip attached (the
third rehearsal of the on-chip-measurement guide, kept as a test).

Interpret mode cannot show what this shows: the ragged varq kernel had
passed every interpret-mode test and was refused here at a 64-token span
for exceeding the 16 MiB scoped VMEM limit. A compile that passes is
not a chip run: nothing here executes, and nothing here is a time.
Whole step programs are compiled by ``tools/chip_rehearsal.py``.
"""
import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import paddle_tpu  # noqa: E402,F401  (x64 + matmul precision as in production)
from paddle_tpu.kernels import attention, norm  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402

# Llama-2-7B: 32 heads of 128, hidden 4096, bf16
H, D, HIDDEN = 32, 128, 4096
BF16, I32 = jnp.bfloat16, jnp.int32
# the serve geometry chip_smoke.py uses
B, PAGE, PAGES_PER_SEQ, POOL = 8, 16, 64, 1024
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one chip of a described v5e:2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert " f64[" not in text
    return text


def _pool():
    return ((POOL, PAGE, H, D), BF16)


def _meta():
    return [((B * PAGES_PER_SEQ,), I32)] * len(pa.RaggedMetaBuilder.FIELDS)


def test_rms_norm(chip):
    _compile(chip, lambda x, w: norm._rms_pallas(x, w, 1e-5),
             ((2048, HIDDEN), BF16), ((HIDDEN,), BF16))


def test_layer_norm(chip):
    _compile(chip, lambda x, w, b: norm._ln_pallas(x, w, b, 1e-5),
             ((2048, HIDDEN), BF16), ((HIDDEN,), BF16), ((HIDDEN,), BF16))


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
def test_flash_forward(chip, kv_heads):
    q = ((2 * H, 2048, D), BF16)
    kv = ((2 * kv_heads, 2048, D), BF16)
    _compile(chip, lambda q, k, v: attention._flash_fwd_pallas(
        q, k, v, SCALE, True, n_heads=H, n_kv_heads=kv_heads), q, kv, kv)


def test_flash_backward(chip):
    x = ((2 * H, 2048, D), BF16)
    lse = ((2 * H, 2048), jnp.float32)
    text = _compile(
        chip, lambda q, k, v, o, lse, do: attention._flash_bwd_pallas(
            q, k, v, o, lse, do, SCALE, True, n_heads=H, n_kv_heads=H),
        x, x, x, x, lse, x)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_paged_attention_block_tables(chip):
    _compile(chip, lambda q, k, v, bt, cl: pa._paged_attention_pallas(
        q, k, v, bt, cl, SCALE),
        ((B, H, D), BF16), _pool(), _pool(),
        ((B, PAGES_PER_SEQ), I32), ((B,), I32))


# The attention of one decode step at the geometries of the benchmark's
# cells: `dsllm7b-chat-open` (MHA, 32 slots x 64 pages on a pool of
# 1536), `mistral7b-sessions-closed` (GQA 4:1, 16 x 256) and
# `granite4h-chat-open` (GQA 4:1, 64 x 128), the last two on a pool of
# 4096 pages of 16 x 8 x 128. This step's K/V is written into the pool,
# then 32 query heads are attended through the block-table kernel. The
# kernel takes the pool as it lies: its 2-D view is a bitcast, nothing
# of the pool's size is copied and no [slots x pages_per_seq, page,
# Hkv, D] table is gathered. A block is 2048 key columns whatever the
# group: 4 pages of 32 KV heads, 16 pages of 8.
@pytest.mark.parametrize("slots,pps,pool,kv_heads", [
    (32, 64, 1536, 32), (16, 256, 4096, 8), (64, 128, 4096, 8)],
    ids=["dsllm7b-chat-open", "mistral7b-sessions-closed",
         "granite4h-chat-open"])
def test_paged_attention_block_tables_cells(chip, slots, pps, pool,
                                            kv_heads):
    ppb = pa.paged_pages_per_block(H, kv_heads, D, PAGE, 2, pps)
    assert ppb * PAGE * kv_heads == pa._BLOCK_KEY_COLUMNS == 2048
    assert pa.paged_block_vmem_bytes(ppb, H, kv_heads, D, PAGE, 2) \
        <= pa._VMEM_SCOPED_BYTES

    def step(kp, vp, bt, cl, q, k, v):
        pidx = bt[jnp.arange(slots, dtype=I32), cl // PAGE]
        kp = kp.at[pidx, cl % PAGE].set(k)
        vp = vp.at[pidx, cl % PAGE].set(v)
        return pa._paged_attention_pallas(q, kp, vp, bt, cl + 1,
                                          SCALE), kp, vp
    pages = ((pool, PAGE, kv_heads, D), BF16)
    new = ((slots, kv_heads, D), BF16)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        pages, pages, ((slots, pps), I32), ((slots,), I32),
        ((slots, H, D), BF16), new, new)]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " f64[" not in text and " s64[" not in text
    # a gathered table is a temporary of slots x pages_per_seq pages
    # (134 MB at 16 x 256, whose shape is the pool's own); the pools are
    # donated and updated where they lie
    table = slots * pps * PAGE * kv_heads * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < table // 16
    if slots * pps != pool:
        assert f"[{slots * pps},{PAGE},{kv_heads},{D}]" not in text
    assert f"bf16[{pool * PAGE * kv_heads},{D}]" in text    # the 2-D view
    for shape in (f"bf16[{pool},{PAGE},{kv_heads},{D}]",
                  f"bf16[{pool * PAGE * kv_heads},{D}]"):
        assert not re.search(r"= " + re.escape(shape) + r"\S* copy\(", text)


# every span bucket the predictor can request at these widths: chunk
# buckets page * 2^k up to the VMEM bound, a speculative-verify span
# (k + 1 = 5), and the single decode token
@pytest.mark.parametrize("span", [1, 5, 16, 32, 64, 128])
def test_paged_attention_ragged_varq(chip, span):
    assert span <= pa.max_varq_span(H, D, PAGE, 2)
    _compile(
        chip, lambda q, k, v, kl, ql, *m:
        pa._paged_attention_ragged_varq_pallas(q, k, v, kl, ql, m, SCALE),
        ((B, span, H, D), BF16), _pool(), _pool(), ((B,), I32), ((B,), I32),
        *_meta())


def test_varq_span_bound_is_the_compilers(chip):
    """The largest bucket `max_varq_span` admits compiles (above); the
    next one is refused by name before the compiler is asked, and by the
    compiler itself when the check is lifted."""
    fit = pa.max_varq_span(H, D, PAGE, 2)
    assert fit == 128
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        ((B, 2 * fit, H, D), BF16), _pool(), _pool(), ((B,), I32),
        ((B,), I32), *_meta())]

    def call(q, k, v, kl, ql, *m):
        return pa._paged_attention_ragged_varq_pallas(q, k, v, kl, ql, m,
                                                      SCALE)
    with pytest.raises(ValueError, match="largest bucket that fits is 128"):
        jax.jit(call).lower(*args)
    limit = pa._VMEM_SCOPED_BYTES
    pa._VMEM_SCOPED_BYTES = 1 << 40
    try:
        with pytest.raises(Exception, match="vmem|RESOURCE_EXHAUSTED"):
            jax.jit(call).lower(*args).compile()
    finally:
        pa._VMEM_SCOPED_BYTES = limit


# The XLA block-table path at the GQA serve geometry of the benchmark's
# `mistral7b-sessions-closed`: 16 slots x 4096 positions, 32 query heads
# on 8 KV heads. Per KV head group the compiler consumes the gathered
# bf16 table as it lies; with the heads repeated it made, each layer, a
# float32 `convert`, a transposing `copy` and a `broadcast
# f32[16,4096,8,4,128]` of it (1 GB for K and again for V).
@pytest.mark.parametrize("span", [None, 8], ids=["decode", "varq8"])
def test_xla_block_table_path_keeps_the_table_bf16(chip, span):
    slots, pps, pool, kv_heads = 16, 256, 4096, 8
    pages = ((pool, PAGE, kv_heads, D), BF16)
    if span is None:
        fn = lambda q, k, v, bt, cl: pa._paged_attention_xla(
            q, k, v, bt, cl, SCALE)
        shapes = (((slots, H, D), BF16), pages, pages,
                  ((slots, pps), I32), ((slots,), I32))
    else:
        fn = lambda q, k, v, bt, kl, ql: pa._paged_attention_varq_xla(
            q, k, v, bt, kl, ql, SCALE)
        shapes = (((slots, span, H, D), BF16), pages, pages,
                  ((slots, pps), I32), ((slots,), I32), ((slots,), I32))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    table = slots * pps * PAGE * kv_heads * D
    sizes = {}
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]+)\]", text):
        sizes.setdefault(math.prod(map(int, dims.split(","))),
                         set()).add(dtype)
    assert " f64[" not in text
    assert sizes[table] == {"bf16"}, sizes[table]
    assert max(sizes) == table, max(sizes)


def test_predictor_refuses_a_span_over_the_bound(monkeypatch):
    """The bound reaches the user at construction: a chunk size whose
    bucket the kernel cannot hold is a ValueError that names it, not a
    compiler refusal at the first long prompt."""
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.inference import ContinuousBatchingPredictor
    from paddle_tpu.kernels import _common
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    # the constructor asks the backend and sees the CPU here: the gate
    # follows the flag alone, as it does on the chip
    monkeypatch.setattr(_common, "use_pallas",
                        lambda: bool(flag_value("use_pallas_kernels")))
    cfg = LlamaConfig.tiny(hidden_size=1024, num_attention_heads=8,
                           num_key_value_heads=8, num_hidden_layers=1,
                           intermediate_size=128, vocab_size=64,
                           tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    fit = pa.max_varq_span(8, 128, 16, 4)
    geometry = dict(max_batch_size=2, page_size=16, max_seq_len=4 * fit)
    assert ContinuousBatchingPredictor(model, prefill_chunk_tokens=fit,
                                       **geometry).span_ragged
    with pytest.raises(ValueError, match="max_varq_span"):
        ContinuousBatchingPredictor(model, prefill_chunk_tokens=2 * fit,
                                    **geometry)


# The serve programs of the benchmark's `granite4h-chat-open` at real
# size (Granite-4.0-H-Small widths, 10 layers, 36 of 72 experts, 64
# slots): what `benchmarks/rehearse.py compile` does for the Llama cells,
# done here because that tool hands a program `pool.k` / `pool.v` alone
# and cannot thread the state pool. The compiler's `memory_analysis()`
# checks the configuration's memory plan; the state update must be ONE
# fusion a Mamba layer writing the donated state in place, and the
# expert matmuls the compiler's grouped kernel (a Mosaic kernel, which
# refuses bf16 operands under the global "highest" precision).
def _cell_predictor(chip, cell):
    """The cell's model with abstract weights behind its predictor, and
    the programs' fixed operands (weights, buffers, caches) as shapes on
    the described chip. Yields (pred, n_params, fixed)."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.lib import harness
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.inference import ContinuousBatchingPredictor
    # code that asks the backend sees the CPU here: the kernel gates
    # follow the flag alone, as they do on the chip
    patch = pytest.MonkeyPatch()
    from paddle_tpu.kernels import (hyper_connections, kda, latent_attention,
                                    sparse_attention)
    for mod in (attention, norm, pa, sparse_attention, latent_attention,
                kda, hyper_connections):
        patch.setattr(mod, "_use_pallas",
                      lambda: bool(flag_value("use_pallas_kernels")))
    cfg = harness.find_cell(root, cell)["cfg"]
    builder = harness.load_module(root, "models", cfg["builder"])
    model, n_params = builder.build(cfg, 0, abstract=True)
    pred = ContinuousBatchingPredictor(model, kv_dtype=cfg["dtype"],
                                       **cfg["serve"])
    pred._ensure_ready()
    sds = lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype,
                                         sharding=chip)
    fixed = jax.tree_util.tree_map(
        sds, (pred._p_vals, pred._b_vals, *pred._cache_args()))
    yield pred, n_params, fixed
    patch.undo()


@pytest.fixture(scope="module")
def granite(chip):
    yield from _cell_predictor(chip, "granite4h-chat-open")


def _compile_program(chip, pred, fixed, fn, *shapes, **typed):
    """`shapes` of int32 operands; then `typed` ones, `name=(shape,
    dtype)`, in the order given."""
    args = [jax.ShapeDtypeStruct(s, I32, sharding=chip) for s in shapes] \
        + [jax.ShapeDtypeStruct(*st, sharding=chip) for st in typed.values()]
    with pred._trace_lock, pred._kernel_scope():
        compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(
            *fixed, *args).compile()
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return compiled.as_text(), live, ma


def test_granite_decode_step_at_real_size(chip, granite):
    pred, n_params, fixed = granite
    assert n_params == 4_757_211_776        # the issue's 4,757 M
    B, pps = pred.B, pred.pages_per_seq
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_decode_step, (B, pps), (B,), (B,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 13.0e9, live              # 12.57 GB when written
    # pages and state rows are updated where they lie
    assert ma.alias_size_in_bytes >= pred.state_pool.nbytes
    state = f"f32[{B + 1},128,64,128]"
    updates = [ln for ln in text.splitlines()
               if " fusion(" in ln and f", {state}" in ln.split(" fusion(")[0]]
    assert len(updates) == 9, len(updates)  # one fusion a Mamba layer
    assert not re.search(r"= " + re.escape(state) + r"\S* copy\(", text)
    assert text.count("ragged-dot-none") >= 20      # 2 a layer
    # the one attention layer decodes through the block-table kernel:
    # no table of every slot's pages is gathered
    assert f"[{B * pps},16,8,128]" not in text
    assert re.search(r"bf16\[%d,32,128\]\S* custom-call\(" % B, text)


def test_granite_largest_prefill_at_real_size(chip, granite):
    pred, _, fixed = granite
    n, bucket = 8, 1024
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page), (n,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 14.5e9, live              # 13.73 GB when written
    assert ma.temp_size_in_bytes < 2.0e9, ma.temp_size_in_bytes


# --- the sparse-attention cell at its real geometry -----------------------
# 32 slots of up to 16384 positions over 16385 pages, 12 layers of 32 / 4
# heads of 128 with a 64-wide index key a token, 16 of 128 experts held.

@pytest.fixture
def pallas_by_flag(monkeypatch):
    """Code that asks the backend sees the CPU here: the kernel gates
    follow the flag alone, as they do on the chip."""
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.kernels import sparse_attention as sa
    for mod in (attention, pa, sa):
        monkeypatch.setattr(mod, "_use_pallas",
                            lambda: bool(flag_value("use_pallas_kernels")))


def test_sparse_decode_kernels_at_the_cells_geometry(chip, pallas_by_flag):
    slots, pps, pool, hkv, j, di = 32, 1024, 16385, 4, 16, 64
    text = _compile(
        chip, lambda q, k, v, ip, qi, w, bt, cl: pa.paged_sparse_attention(
            q, k, v, ip, qi, w, bt, cl, 2048, SCALE),
        ((slots, H, D), BF16), ((pool, PAGE, hkv, D), BF16),
        ((pool, PAGE, hkv, D), BF16), ((pool, PAGE, 128), BF16),
        ((slots, j, di), BF16), ((slots, j), jnp.float32),
        ((slots, pps), I32), ((slots,), I32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " s64[" not in text
    # neither pool is gathered, copied or widened on the way in
    for shape in (f"bf16[{pool},{PAGE},{hkv},{D}]",
                  f"bf16[{pool},{PAGE},128]"):
        assert not re.search(r"= " + re.escape(shape) + r"\S* copy\(", text)
    assert f"[{slots},{pps},{PAGE}," not in text


@pytest.mark.parametrize("n,bucket", [(1, 4096), (2, 16384)])
def test_sparse_prefill_attention_at_the_cells_buckets(chip, pallas_by_flag,
                                                       n, bucket):
    from paddle_tpu.kernels import sparse_attention as sa
    hkv, j, di = 4, 16, 64
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        ((n, bucket, H, D), BF16), ((n, bucket, hkv, D), BF16),
        ((n, bucket, hkv, D), BF16), ((n, bucket, j, di), BF16),
        ((n, bucket, j), jnp.float32), ((n, bucket, di), BF16),
        ((n, bucket), jnp.bool_))]
    compiled = jax.jit(lambda *a: sa.sparse_prefill_attention(
        *a, topk=2048, scale=SCALE, chunk=512)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " f64[" not in text and " s64[" not in text
    # nothing of [bucket, bucket] extent: a chunk of 512 queries at most
    assert f"[{n},{bucket},{bucket}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.fixture(scope="module")
def keye(chip):
    yield from _cell_predictor(chip, "keye2-longprompt-open")


def test_keye_decode_step_at_real_size(chip, keye):
    pred, n_params, fixed = keye
    assert n_params == 1_240_586_752        # the issue's 1,241 M
    B, pps = pred.B, pred.pages_per_seq
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_decode_step, (B, pps), (B,), (B,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 10.5e9, live
    # K, V and index pages are updated where they lie
    pool = sum(a.nbytes for a in pred.pool.k + pred.pool.v + pred.pool.index)
    assert ma.alias_size_in_bytes >= pool
    # two kernels a layer (index scores, masked attention) and the
    # compiler's grouped matmuls; no slot's table is gathered
    assert text.count('custom_call_target="tpu_custom_call"') >= 24
    assert text.count("ragged-dot-none") >= 24
    assert f"[{B},{pps},16," not in text


def test_keye_largest_prefill_at_real_size(chip, keye):
    pred, _, fixed = keye
    n, bucket = 2, 16384
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page))
    assert " f64[" not in text and " s64[" not in text
    assert f"[{n},1,{bucket},{bucket}]" not in text
    assert f"[{n},{bucket},{pred.model.config.vocab_size}]" not in text
    assert live < 14.5e9, live


# --- the linear-attention cell at its real geometry -------------------------
# 32 slots of up to 16384 positions over 25601 pages; 7 layers: six KDA (a
# float32 [32, 128, 128] state and a conv window over 12288 channels a
# slot) and one MLA (a 576-wide latent row a token on 640 lanes), two
# dense and five expert layers with 128 of 512 experts held.

@pytest.fixture(scope="module")
def ling(chip):
    yield from _cell_predictor(chip, "ling3f-longdoc-open")


def test_ling_decode_step_at_real_size(chip, ling):
    pred, n_params, fixed = ling
    assert n_params == 4_454_368_704        # the issue's 4.45 B
    B, pps = pred.B, pred.pages_per_seq
    # no K/V anywhere: latent rows in place of keys, nothing for values
    assert all(v is None for v in pred.pool.v)
    assert [a.shape for a in pred.pool.k] == [(25601, 16, 640)]
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_decode_step, (B, pps), (B,), (B,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 11.0e9, live
    # latent pages and state rows are updated where they lie
    pool = pred.state_pool.nbytes + sum(a.nbytes for a in pred.pool.latent)
    assert ma.alias_size_in_bytes >= pool
    state = f"f32[{B + 1},32,128,128]"
    assert not re.search(r"= " + re.escape(state) + r"\S* copy", text)
    # one state kernel a KDA layer, its first output the donated rows
    assert len(re.findall(r"%kda\.state_update[.\d]* = \(" + re.escape(state),
                          text)) == 6
    # the MLA layer decodes through the latent kernel: no slot's table
    # of rows is gathered
    assert f"[{B},{pps},16,640]" not in text
    assert re.search(r"bf16\[%d,32,640\]\S* custom-call\(" % B, text)
    assert text.count("ragged-dot-none") >= 10      # 2 an expert layer


def test_ling_largest_prefill_at_real_size(chip, ling):
    pred, _, fixed = ling
    n, bucket = 2, 16384
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page), (n,))
    assert " f64[" not in text and " s64[" not in text
    assert f"[{n},1,{bucket},{bucket}]" not in text
    assert f"[{n},{bucket},{pred.model.config.vocab_size}]" not in text
    assert live < 14.5e9, live
    # the expert layer routes the 2 x 16384 tokens in the two pieces its
    # rule gives 128 of 512 experts top-8: 16384 tokens x 8 rows a
    # grouped matmul, where pieces of 2048 tokens made it
    # `bf16[16384,1536]`
    c = pred.model.config
    piece = 16384
    rows = set(re.findall(
        r"= bf16\[(\d+),%d\]\S* custom-call\(" % (2 * c.moe_intermediate_size),
        "\n".join(ln for ln in text.splitlines() if "ragged-dot" in ln)))
    assert rows == {str(piece * c.num_experts_per_tok)}, rows


# --- the other cells' decode steps have not moved --------------------------
# sha256 of each cell's lowered decode step, every Mosaic kernel's body
# re-printed without its debug locations (they carry a checkout's paths
# and lines), and beside it the sha256 of those kernel bodies alone. A
# change to code these cells share (the predictor's loops over the
# layout, the paged contracts, `_paged_kernel`, the dropless expert
# layer) must leave these texts as they are, or say why it moved them.
# The STEPS of the three cells whose layers keep K/V pages without an
# indexer are those of the parents named below; the other three are PR
# 43's: their page entries carry `live` and their decode contracts
# attend over `attend_lens` (one compare a program, one select a layer
# more). The KERNELS are all still the named parents': PR 43 changed no
# kernel body.
DECODE_STEP_AT_PARENT = {
    # at the parent of PR 33 (0b146e3)
    "dsllm7b-chat-open": (
        "f55762f720b5726f6024dd989156d24969fce41c0163fbbddb32bb9f052a812c",
        "2a14a710099dfbb6e301eb1d1beba13a263bbd015d6b3b7586787d675d14783e"),
    "mistral7b-sessions-closed": (
        "d345aebdde0457cc21ca9a1a03dfb08a121547470e722bbdca52590450005a29",
        "594d398f85f468160843729c2f187ee988ef39172b455dc03b13a398c5c57fbb"),
    "granite4h-chat-open": (
        "64d706b3c231ec57bbb1bc8f8bb7529b05154f10574fcce7003441b3d0e0e1bf",
        "875d0e8f7a93f246e5fadd9b3a3435163f29e361c70ea559fa9c971c5b99f256"),
    # kernels at the parent of PR 35 (d1f5296): `dropless.py` takes the
    # routing rule as an argument and the long prefill is asked for by
    # the model
    "keye2-longprompt-open": (
        "1d1418acb9e3a1e70aa983559b963471a55d79ad3362830c77a673de1dc6f02d",
        "8b24b88e0611e0ff3f64c771dbfb8ed4fd8b3c3c9c6f0be35ae9277d1bd19a24"),
    # kernels at the parent of PR 41 (f794e08): the latent kernel takes
    # a query span, and a span of one lowers as the kernel did
    "ling3f-longdoc-open": (
        "dbc29c2f033ed718d80f12409ddc89289560bb86b8df14b1c5e47c355be5efc5",
        "1d9856743feb314674f67cb42994304f9de1d81ef97df5a058575bb67df7b812"),
    "glm5-longprompt-open": (
        "ee7119b545c0ca1bef04a0b560a57cb050db627387c4e3f24bc6dedbe0fb9b94",
        "347ba0f698feef869e3002b80a5e696685f1aa06a892ed3aee8e1c23f3f69f6d"),
}


def _without_debug_locations(text):
    import base64
    import json
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(m):
        cfg = json.loads(m.group(1).replace("\\22", '"'))
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            cfg["custom_call_config"]["body"] = ir.Module.parse(
                base64.b64decode(cfg["custom_call_config"]["body"])
            ).operation.get_asm(enable_debug_info=False)
        return "backend_config = " + json.dumps(cfg, sort_keys=True)

    return re.sub(r'backend_config = "(\{\\22custom_call_config.*?\})"',
                  body, text)


@pytest.mark.parametrize("cell", sorted(DECODE_STEP_AT_PARENT))
def test_decode_step_lowers_as_at_the_parent(chip, cell):
    import hashlib
    gen = _cell_predictor(chip, cell)
    pred, _, fixed = next(gen)
    try:
        args = [jax.ShapeDtypeStruct(s, I32, sharding=chip)
                for s in ((pred.B, pred.pages_per_seq), (pred.B,),
                          (pred.B,))]
        with pred._trace_lock, pred._kernel_scope():
            text = jax.jit(pred._raw_decode_step,
                           donate_argnums=(2, 3)).lower(
                *fixed, *args).as_text()
    finally:
        gen.close()
    assert "custom_call_config" in text
    sha = lambda t: hashlib.sha256(t.encode()).hexdigest()  # noqa: E731
    clean = _without_debug_locations(text)
    kernels = "\n".join(re.findall(r"backend_config = (\{.*)", clean))
    assert (sha(clean), sha(kernels)) == DECODE_STEP_AT_PARENT[cell]


# --- the latent-attention-under-an-indexer cell at its real geometry --------
# 32 slots of up to 16384 positions over 16385 pages; 6 layers of 64 heads
# on a 576-wide latent row a token (640 lanes) with a 128-wide index key
# beside it, 32 index heads; one dense and five expert layers, 16 of 256
# experts held.

def test_sparse_latent_decode_kernels_at_the_cells_geometry(chip,
                                                            pallas_by_flag,
                                                            monkeypatch):
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.kernels import latent_attention as la
    monkeypatch.setattr(la, "_use_pallas",
                        lambda: bool(flag_value("use_pallas_kernels")))
    slots, pps, pool, heads, j, di = 32, 1024, 16385, 64, 32, 128
    text = _compile(
        chip, lambda q, rows, ip, qi, w, bt, cl:
        la.paged_sparse_latent_attention(q, rows, ip, qi, w, bt, cl, 2048,
                                         SCALE),
        ((slots, heads, 576), BF16), ((pool, PAGE, 640), BF16),
        ((pool, PAGE, 128), BF16), ((slots, j, di), BF16),
        ((slots, j), jnp.float32), ((slots, pps), I32), ((slots,), I32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " s64[" not in text
    # neither pool is gathered, copied or widened on the way in
    for shape in (f"bf16[{pool},{PAGE},640]", f"bf16[{pool},{PAGE},128]"):
        assert not re.search(r"= " + re.escape(shape) + r"\S* copy\(", text)
    assert f"[{slots},{pps},{PAGE}," not in text


@pytest.fixture(scope="module")
def glm(chip):
    yield from _cell_predictor(chip, "glm5-longprompt-open")


def test_glm_decode_step_at_real_size(chip, glm):
    pred, n_params, fixed = glm
    assert n_params == 4_727_340_800        # the issue's 4.727 B
    B, pps = pred.B, pred.pages_per_seq
    # no K/V anywhere: latent rows and index keys under the same page ids
    assert all(v is None for v in pred.pool.v)
    assert [a.shape for a in pred.pool.k] == [(16385, 16, 640)] * 6
    assert [a.shape for a in pred.pool.index] == [(16385, 16, 128)] * 6
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_decode_step, (B, pps), (B,), (B,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 12.5e9, live
    # latent and index pages are updated where they lie
    pool = sum(a.nbytes for a in pred.pool.latent + pred.pool.index)
    assert ma.alias_size_in_bytes >= pool
    # two kernels a layer (index scores, masked latent attention), the
    # attention's output [slots, heads, lanes]; no slot's table of rows
    # is gathered
    assert text.count('custom_call_target="tpu_custom_call"') >= 12
    assert len(re.findall(r"bf16\[%d,64,640\]\S* custom-call\(" % B,
                          text)) == 6
    assert f"[{B},{pps},16,640]" not in text
    assert text.count("ragged-dot-none") >= 10      # 2 an expert layer


@pytest.mark.parametrize("bucket", [8192, 16384])
def test_glm_prefill_at_real_size(chip, glm, bucket):
    pred, _, fixed = glm
    n = pred._prefill_rows
    assert n == 1
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page))
    assert " f64[" not in text and " s64[" not in text
    assert f"[{n},1,{bucket},{bucket}]" not in text
    assert f"[{n},{bucket},{pred.model.config.vocab_size}]" not in text
    # under the chip's 16 GB with room for the allocator
    assert live < 14.5e9, live
    # nothing of [heads, bucket] extent but the decompressed keys and
    # values: the queries are made a chunk at a time
    assert f"bf16[{n},{bucket},64,256]" not in text
    # the masked flash kernel takes a chunk's 512 queries in one tile
    # (`rep` 1: every tile would read a head's keys again)
    assert f"bf16[{n},64,1,512,256]" in text


# --- the self-drafting cell at its real geometry ----------------------------
# 32 slots of up to 4096 positions over 8193 pages; 5 trunk layers and the
# MTP layer of 128 heads on a 576-wide latent row a token (640 lanes); a
# verify span of two queries a slot: 256 query rows against a slot's rows.

@pytest.mark.parametrize("span", [1, 2])
def test_latent_span_kernel_at_the_cells_geometry(chip, pallas_by_flag,
                                                  monkeypatch, span):
    from paddle_tpu.framework.flags import flag_value
    from paddle_tpu.kernels import latent_attention as la
    monkeypatch.setattr(la, "_use_pallas",
                        lambda: bool(flag_value("use_pallas_kernels")))
    slots, pps, pool, heads = 32, 256, 8193, 128
    text = _compile(
        chip, lambda q, rows, bt, cl: la.paged_latent_attention(
            q, rows, bt, cl, SCALE, span=span),
        ((slots, span * heads, 576), BF16), ((pool, PAGE, 640), BF16),
        ((slots, pps), I32), ((slots,), I32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " s64[" not in text
    # the span's queries ride the row axis of ONE call: a slot's rows
    # are read once, and the pool is taken as it lies
    assert re.search(r"bf16\[%d,%d,640\]\S* custom-call\("
                     % (slots, span * heads), text)
    assert not re.search(r"= bf16\[%d,%d,640\]\S* copy\(" % (pool, PAGE),
                         text)
    assert f"[{slots},{pps},{PAGE}," not in text


@pytest.fixture(scope="module")
def pangu(chip):
    yield from _cell_predictor(chip, "pangu-reason-open")


def test_pangu_tick_at_real_size(chip, pangu):
    """The tick's ONE program: the verify span through the trunk, then
    the draft pass through the MTP layer."""
    pred, n_params, fixed = pangu
    assert n_params == 6_037_863_680        # the issue's 6037.7 M, norms in
    B, pps = pred.B, pred.pages_per_seq
    assert pred._drafter == (1, 5)
    assert all(v is None for v in pred.pool.v)
    assert [a.shape for a in pred.pool.k] == [(8193, 16, 640)] * 6
    pool = sum(a.nbytes for a in pred.pool.latent)
    # the host's table, positions and span; which slots take them; the
    # tick before's positions and span, chained on the device
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_mtp_step, (B, pps), (B,), (B, 2),
        fresh=((B,), jnp.bool_), ctx_chain=((B,), I32),
        span_chain=((B, 2), I32))
    assert " f64[" not in text and " s64[" not in text
    assert live < 13.6e9, live
    assert ma.alias_size_in_bytes >= pool       # rows written in place
    # one span kernel a trunk layer and one in the draft pass, [slots,
    # 2 x heads, lanes]; no slot's table of rows is gathered
    assert len(re.findall(r"bf16\[%d,256,640\]\S* custom-call\(" % B,
                          text)) == 6
    assert f"[{B},{pps},16,640]" not in text
    assert text.count("ragged-dot-none") >= 10      # 2 an expert layer


@pytest.mark.parametrize("bucket", [256, 2048])
def test_pangu_prefill_at_real_size(chip, pangu, bucket):
    pred, _, fixed = pangu
    n = pred._prefill_rows
    assert n == 1
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page))
    assert " f64[" not in text and " s64[" not in text
    assert f"[{n},1,{bucket},{bucket}]" not in text
    assert f"[{n},{bucket},{pred.model.config.vocab_size}]" not in text
    # under the chip's 16 GB with room for the allocator
    assert live < 14.5e9, live
    # a flash kernel a layer: five trunk layers and the MTP layer
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


def test_pangu_reference_programs_fit_the_chip(chip):
    """The cell's plain reference at the published widths, a sequence
    padded to 4096: its weights are drawn by programs of their own (in
    the layer's program the draws' temporaries and the activations took
    24 GB on the chip, PR 41) and every program keeps under 4 GB."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.lib import harness
    ref = harness.load_module(root, "reference", "pangu_ultra_moe")
    cfg = harness.find_cell(root, "pangu-reason-open")["cfg"]
    cfg_s = ref._static(cfg)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    key, index, x = on_chip((
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), I32),
        jax.ShapeDtypeStruct((4096, cfg["hidden_size"]), jnp.float32)))

    def live(compiled):
        ma = compiled.memory_analysis()
        return ma.argument_size_in_bytes + ma.output_size_in_bytes \
            + ma.temp_size_in_bytes

    for kind in ("attn", "dense"):
        w = on_chip(jax.eval_shape(
            lambda k, i: getattr(ref.pw, kind)(cfg, k, i), key, index))
        assert live(ref._weights.lower(key, index, kind, cfg_s).compile()) \
            < 1.0e9
        step = ref._attend.lower(x, w, cfg_s, None) if kind == "attn" \
            else ref._feed_forward.lower(x, w, key, index, True, cfg_s,
                                         "int8")
        assert live(step.compile()) < 4.0e9


# --- the four-stream cell at its real geometry ------------------------------
# 32 slots of up to 8192 positions over 16385 pages; 6 layers of 32 heads
# on a 576-wide latent row a token (640 lanes); four residual streams of
# 3584 a token, read and mixed by two kernels at each of 12 sublayers;
# all 64 experts and the whole 131072-wide vocabulary.

@pytest.mark.parametrize("rows", [32, 2048, 8192],
                         ids=["step", "prompt2048", "prompt8192"])
def test_mhc_kernels_at_the_cells_geometry(chip, rows):
    """`mhc_pre` and `mhc_post` at a decode step's rows and a prompt's:
    whole rows of 4 x 3584 numbers a block (the scoped VMEM limit is
    raised for them), the streams written back in place."""
    from paddle_tpu.kernels import hyper_connections as hc
    n, c = 4, 3584
    text = _compile(
        chip, lambda x, phi, a, b: hc._mhc_pre_pallas(
            x, phi, a, b, n, 20, 1e-6, 1e-6, (-30.0, 30.0), False),
        ((rows, n * c), BF16), ((n * c, 24), BF16), ((3,), jnp.float32),
        ((24,), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"\(bf16\[%d,%d\]\S*, f32\[%d,128\]\S*\) custom-call\("
                     % (rows, c, rows), text)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        ((rows, n * c), BF16), ((rows, c), BF16), ((rows, 128), jnp.float32))]
    compiled = jax.jit(lambda x, f, cf: hc._mhc_post_pallas(x, f, cf, n,
                                                            False),
                       donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == rows * n * c * 2   # in place
    assert ma.temp_size_in_bytes == 0


@pytest.fixture(scope="module")
def xing(chip):
    yield from _cell_predictor(chip, "xing4-code-open")


def test_xing_decode_step_at_real_size(chip, xing):
    pred, n_params, fixed = xing
    # 128.2 M dense layer + 5 x 745.0 M expert layers + 939.5 M of
    # embedding and head (the issue's 4792.6 M, norms and biases in)
    assert n_params == 4_792_669_828
    B, pps = pred.B, pred.pages_per_seq
    assert pred._drafter is None and pred._prefill_rows == 1
    assert all(v is None for v in pred.pool.v)
    assert [a.shape for a in pred.pool.k] == [(16385, 16, 640)] * 6
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_decode_step, (B, pps), (B,), (B,))
    assert " f64[" not in text and " s64[" not in text
    assert live < 15.5e9, live
    pool = sum(a.nbytes for a in pred.pool.latent)
    assert ma.alias_size_in_bytes >= pool       # rows written in place
    # a layer: two `mhc.pre`, two `mhc.post`, one latent decode kernel
    assert len(re.findall(r"bf16\[%d,14336\]\S* custom-call\(.*tpu_custom_call"
                          % B, text)) == 12
    assert len(re.findall(r"\(bf16\[%d,3584\]\S*, f32\[%d,128\]\S*\) "
                          r"custom-call\(" % (B, B), text)) == 12
    assert len(re.findall(r"bf16\[%d,32,640\]\S* custom-call\(" % B,
                          text)) == 6
    assert f"[{B},{pps},16,640]" not in text
    assert text.count("ragged-dot-none") >= 10      # 2 an expert layer


@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096, 8192])
def test_xing_prefill_at_real_size(chip, xing, bucket):
    """The long prefill at every bucket the cell warms."""
    pred, _, fixed = xing
    n = pred._prefill_rows
    text, live, ma = _compile_program(
        chip, pred, fixed, pred._raw_prefill, (n, bucket), (n, bucket),
        (n,), (n, bucket // pred.page))
    assert " f64[" not in text and " s64[" not in text
    assert f"[{n},1,{bucket},{bucket}]" not in text
    assert f"[{n},{bucket},{pred.model.config.vocab_size}]" not in text
    assert live < 15.5e9, live
    # a layer: the flash kernel and the four stream kernels
    assert text.count('custom_call_target="tpu_custom_call"') >= 30
    # (the compiler's own `ConcatBitcast` custom calls give the same shape)
    assert len(re.findall(r"bf16\[%d,14336\]\S* custom-call\(.*tpu_custom_call"
                          % bucket, text)) == 12


def test_xing_reference_programs_fit_the_chip(chip):
    """The cell's plain reference at the published widths, a sequence
    padded to 8192 with four float32 streams a token: its weights are
    drawn by programs of their own and every program keeps under 4
    GB."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.lib import harness
    ref = harness.load_module(root, "reference", "xing_moe")
    cfg = harness.find_cell(root, "xing4-code-open")["cfg"]
    cfg_s = ref._static(cfg)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    key, index, x = on_chip((
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), I32),
        jax.ShapeDtypeStruct((8192, cfg["hc_mult"], cfg["hidden_size"]),
                             jnp.float32)))

    def live(compiled):
        ma = compiled.memory_analysis()
        return ma.argument_size_in_bytes + ma.output_size_in_bytes \
            + ma.temp_size_in_bytes

    maps = on_chip(jax.eval_shape(
        lambda k, i: ref.xw.mhc(cfg, k, i, "attn"), key, index))
    assert live(ref._weights.lower(key, index, "mhc", cfg_s,
                                   sub="attn").compile()) < 1.0e9
    for kind in ("attn", "moe"):
        w = on_chip(jax.eval_shape(
            lambda k, i: getattr(ref.xw, kind)(cfg, k, i), key, index))
        assert live(ref._weights.lower(key, index, kind, cfg_s).compile()) \
            < 1.0e9
        step = ref._attend.lower(x, maps, w, cfg_s, None) if kind == "attn" \
            else ref._feed_forward.lower(x, maps, w, key, index, False, cfg_s,
                                         "int8")
        assert live(step.compile()) < 4.0e9
