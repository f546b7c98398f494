"""The block-table paged decode kernel (`kernels.paged_attention.
_paged_kernel`) for grouped-query heads, in interpret mode on the CPU:
parity with the XLA block-table path over group ratios, pool dtypes and
cached lengths around the page and block edges; a custom scale; two
tensor-parallel shards on virtual devices; what the gate admits for
which kernel; the block size's derivation; and the same tokens out of
the predictor with the kernel and with the XLA path, for a GQA Llama
(prefix cache and suffix prefill on) and the tiny hybrid model.

What the TPU's compiler makes of the kernel at the benchmark's
geometries is tests/test_chip_compile.py's.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.kernels import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 2 KV heads of 128, pages of 8 tokens, 8 pages a slot; blocks are cut
# to 4 pages (32 tokens, 64 key columns) so that a table is two blocks
HKV, D, PAGE, PPS, POOL = 2, 128, 8, 8, 40
BLOCK_COLUMNS = 4 * PAGE * HKV
BLOCK, TABLE = 4 * PAGE, PPS * PAGE
CONTEXTS = {
    "empty": [0], "one": [1], "page-less-one": [PAGE - 1], "page": [PAGE],
    "block-less-one": [BLOCK - 1], "block": [BLOCK], "block-and-one":
    [BLOCK + 1], "table": [TABLE],
    "mixed": [0, 1, PAGE - 1, PAGE, BLOCK - 1, BLOCK, BLOCK + 1, TABLE],
}


@pytest.fixture
def interpret():
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    yield
    set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(pa, "_BLOCK_KEY_COLUMNS", BLOCK_COLUMNS)


def _case(lens, h, hkv=HKV, dtype="float32", seed=0, pool=POOL):
    """One slot a length, on distinct pages in a shuffled order; the
    rest of a table points at page 0, as the predictor's trash page."""
    rs = np.random.RandomState(seed)
    lens = np.asarray(lens, np.int32)
    rnd = lambda *shape: jnp.asarray(
        rs.randn(*shape).astype(np.float32)).astype(dtype)
    q = rnd(len(lens), h, D)
    kp, vp = rnd(pool, PAGE, hkv, D), rnd(pool, PAGE, hkv, D)
    bt = np.zeros((len(lens), PPS), np.int32)
    free = list(rs.permutation(np.arange(1, pool)))
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // PAGE)):
            bt[b, j] = free.pop()
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


def _oracle(q, kp, vp, bt, lens, scale):
    """The XLA block-table path; a slot with nothing cached gives zeros
    from the kernels (the XLA path attends uniformly there)."""
    ref = pa._paged_attention_xla(q, kp, vp, bt, lens, scale)
    return np.asarray(jnp.where((lens > 0)[:, None, None], ref, 0)
                      .astype("float32"))


def _fallbacks():
    """{(kernel, reason): count} of `kernels.pallas_fallbacks`."""
    from paddle_tpu.observability import metrics
    m = metrics.get_registry().get("kernels.pallas_fallbacks")
    return {} if m is None else {
        (x.labels.get("kernel"), x.labels.get("reason")): x.value
        for x in m.samples() if x.value}


def _atol(dtype, vp):
    # float32: accumulation order. bf16: P and the output are each
    # rounded to the dtype on both sides, against values up to max |v|
    if dtype == "float32":
        return 2e-5
    return 2 * float(jnp.finfo(dtype).eps) * float(
        np.abs(np.asarray(vp.astype("float32"))).max())


@pytest.mark.parametrize("ctx", list(CONTEXTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rep", [1, 2, 4, 8])
def test_kernel_matches_the_xla_block_table_path(small_blocks, rep, dtype,
                                                 ctx):
    q, kp, vp, bt, lens = _case(CONTEXTS[ctx], HKV * rep, dtype=dtype)
    assert pa.paged_pages_per_block(HKV * rep, HKV, D, PAGE,
                                    q.dtype.itemsize, PPS) == 4
    scale = D ** -0.5
    out = pa._paged_attention_pallas(q, kp, vp, bt, lens, scale,
                                     interpret=True)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(out.astype("float32")),
        _oracle(q, kp, vp, bt, lens, scale), rtol=0, atol=_atol(dtype, vp))


def test_a_block_need_not_divide_the_table(monkeypatch):
    """Three pages a slot: the one block is cut to 2 pages, the second
    block re-reads the last live page for the ordinal past the table."""
    monkeypatch.setattr(pa, "_BLOCK_KEY_COLUMNS", 2 * PAGE * HKV)
    q, kp, vp, bt, lens = _case([3 * PAGE, 2 * PAGE + 1, 5], 8)
    bt = bt[:, :3]
    out = pa._paged_attention_pallas(q, kp, vp, bt, lens, 0.1,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               _oracle(q, kp, vp, bt, lens, 0.1),
                               rtol=0, atol=2e-5)


def test_custom_scale_through_the_public_entry(interpret, small_blocks):
    """Granite's attention multiplier (1/128 where 1/sqrt(128) is the
    default), through `paged_attention` and its gate."""
    q, kp, vp, bt, lens = _case(CONTEXTS["mixed"], 8)
    out = pa.paged_attention(q, kp, vp, bt, lens, scale=1.0 / 128)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, 1.0 / 128),
        rtol=0, atol=2e-5)
    other = _oracle(q, kp, vp, bt, lens, D ** -0.5)
    assert np.abs(np.asarray(out) - other).max() > 1e-3


def test_two_tensor_parallel_shards(interpret, small_blocks):
    """16 query heads on 4 KV heads over 'model' = 2: a shard holds 8
    query heads, the groups of its own 2 KV heads (`hkv % tp`,
    `(h // tp) % 8`), and `partitioned` runs the kernel a shard."""
    from paddle_tpu.distributed.fleet.hybrid.plan import HybridParallelPlan
    from paddle_tpu.kernels._common import kernel_partition_scope
    from paddle_tpu.observability import metrics
    mesh = HybridParallelPlan.from_spec("model=2", zero_stage=0).build_mesh(
        devices=jax.devices()[:2])
    q, kp, vp, bt, lens = _case(CONTEXTS["mixed"], 16, hkv=4)
    want = _oracle(q, kp, vp, bt, lens, D ** -0.5)
    assert pa.paged_gate_reason("paged_attention", 16, 4, D, tp=2) is None
    metrics.get_registry().reset()
    with kernel_partition_scope(mesh):
        text = jax.jit(pa.paged_attention).lower(
            q, kp, vp, bt, lens).as_text()
        out = jax.jit(pa.paged_attention)(q, kp, vp, bt, lens)
    assert _fallbacks() == {}
    # the kernel ran a shard: 8 query heads on its 2 KV heads' pages
    assert "sdy.manual_computation" in text
    assert f"tensor<{POOL * PAGE * 2}x{D}xf32>" in text
    np.testing.assert_allclose(np.asarray(out), want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("kernel,h,hkv,d,tp,reason", [
    ("paged_attention", 32, 8, 128, 1, None),
    ("paged_attention", 32, 32, 128, 1, None),
    ("paged_attention", 24, 8, 128, 1, None),
    ("paged_attention", 32, 12, 128, 1, "gqa_ratio"),
    ("paged_attention_ragged_varq", 32, 32, 128, 1, None),
    ("paged_attention_ragged_varq", 32, 8, 128, 1, "gqa_ratio"),
    ("paged_attention", 32, 8, 64, 1, "head_dim_tiling"),
    ("paged_attention", 12, 4, 128, 1, "head_count_tiling"),
    ("paged_attention", 32, 8, 128, 2, None),
    ("paged_attention", 32, 8, 128, 8, "tp_head_shard"),    # 4 heads a shard
    ("paged_attention", 32, 8, 128, 3, "tp_head_shard"),
    ("paged_attention", 48, 2, 128, 4, "tp_head_shard"),    # hkv % tp
], ids=lambda v: str(v))
def test_gate_reasons(kernel, h, hkv, d, tp, reason):
    assert pa.paged_gate_reason(kernel, h, hkv, d, tp) == reason


def test_gate_admits_a_group_for_the_kernel_that_takes_one():
    """GQA runs the block-table kernel and is still counted as
    `gqa_ratio` for the ragged varq kernel."""
    from paddle_tpu.observability import metrics
    q = jnp.zeros((2, 8, 128))
    pages = jnp.zeros((4, 8, 2, 128))
    metrics.get_registry().reset()
    assert pa._paged_gate("paged_attention", q, pages, pages, True)
    assert _fallbacks() == {}
    assert not pa._paged_gate("paged_attention_ragged_varq", q[:, None],
                              pages, pages, True)
    counted = {("paged_attention_ragged_varq", "gqa_ratio"): 1}
    assert _fallbacks() == counted
    # not wanted (flags off, not interpret): no kernel, nothing counted
    assert not pa._paged_gate("paged_attention", q, pages, pages, False)
    assert _fallbacks() == counted


@pytest.mark.parametrize("h,hkv,d,page,itemsize,pps,want", [
    (32, 8, 128, 16, 2, 256, 16),     # mistral7b-sessions-closed
    (32, 8, 128, 16, 2, 128, 16),     # granite4h-chat-open
    (32, 32, 128, 16, 2, 64, 4),      # MHA at Llama-2-7B widths
    (32, 8, 128, 16, 2, 4, 4),        # never more than a slot's table
    (8, 1, 128, 16, 2, 256, 128),     # MQA: many pages make 2048 columns
    (64, 8, 256, 16, 4, 256, 16),     # float32 at head size 256: 11.6 MB
    (64, 64, 256, 16, 4, 256, 2),
], ids=lambda v: str(v))
def test_pages_per_block_derivation(h, hkv, d, page, itemsize, pps, want):
    ppb = pa.paged_pages_per_block(h, hkv, d, page, itemsize, pps)
    assert ppb == want
    assert ppb * page * hkv <= pa._BLOCK_KEY_COLUMNS or ppb == 1
    assert pa.paged_block_vmem_bytes(ppb, h, hkv, d, page, itemsize) \
        <= pa._VMEM_SCOPED_BYTES


def test_vmem_limit_bounds_the_block(monkeypatch):
    """With the column target out of the way the scoped-VMEM limit is
    what stops the doubling, as it stops `max_varq_span`'s."""
    monkeypatch.setattr(pa, "_BLOCK_KEY_COLUMNS", 1 << 30)
    ppb = pa.paged_pages_per_block(32, 8, 128, 16, 2, 4096)
    need = lambda n: pa.paged_block_vmem_bytes(n, 32, 8, 128, 16, 2)
    assert need(ppb) <= pa._VMEM_SCOPED_BYTES < need(2 * ppb)
    # K and V blocks in two buffers each are the largest share
    assert need(ppb) > 2 * 2 * ppb * 16 * 8 * 128 * 2


# ------------------------------------------- through the predictor --

def _serve(model, prompts, kernel, **kw):
    """Tokens and the predictor, served with the Pallas kernels in
    interpret mode (`kernel`) or with the flags off (the XLA path)."""
    from paddle_tpu.inference import ContinuousBatchingPredictor
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": kernel, "pallas_interpret": kernel})
    try:
        pred = ContinuousBatchingPredictor(model, **kw)
        return [pred.generate(p, max_new_tokens=8) for p in prompts], pred
    finally:
        set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


def test_gqa_llama_serves_the_same_tokens_with_the_kernel():
    """A GQA Llama (8 query heads on 2 KV heads of 128) through prefill,
    a prefix-cache hit with suffix prefill, and decode: the kernel takes
    every decode step (no fallback counted) and the tokens are the XLA
    path's."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import metrics
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=1024, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        max_position_embeddings=128))
    rng = np.random.RandomState(0)
    shared = rng.randint(2, 128, (20,)).tolist()
    rounds = [[shared + rng.randint(2, 128, (n,)).tolist()
               for n in (3, 9)],
              [shared + rng.randint(2, 128, (n,)).tolist()
               for n in (5, 1, 12)]]
    geometry = dict(max_batch_size=2, page_size=8, max_seq_len=64)
    want, ref = _serve(model, rounds, False, **geometry)
    metrics.get_registry().reset()
    got, pred = _serve(model, rounds, True, **geometry)
    assert got == want
    assert pred.prefix_cache is not None
    assert pred.stats["prefix_partial_hits"] + pred.stats["prefix_hits"] >= 3
    assert _fallbacks() == {}


def test_hybrid_model_serves_the_same_tokens_with_the_kernel():
    """tests/test_granite_hybrid.py's tiny hybrid at a head size the
    kernel takes (8 query heads on 2 KV heads of 128, scale 0.2)."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import harness
    from tests.test_granite_hybrid import CFG, GEO, SEED
    cfg = dict(CFG, hidden_size=1024, num_attention_heads=8,
               mamba_n_heads=16, mamba_d_head=128, initializer_range=0.05)
    model = harness.load_module(ROOT, "models", "granite_hybrid").build(
        cfg, SEED)[0]
    rng = np.random.default_rng(3)
    prompts = [[rng.integers(2, cfg["vocab_size"], n).tolist()
                for n in (5, 17, 9, 30, 12)]]
    want, _ = _serve(model, prompts, False, **GEO)
    got, pred = _serve(model, prompts, True, **GEO)
    assert got == want
    assert pred.state_pool is not None
