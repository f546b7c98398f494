"""Which paged kernel a decode program attends through: the block-table
kernel (`kernels.paged_attention._paged_kernel`) for every head geometry
its gate admits, MHA included, with no ragged metadata among the decode
programs' operands; the span programs (mixed, verify) of an MHA model
keep the ragged varq kernel and its metadata; `use_ragged`, the option
that once chose another decode kernel, is an ignored keyword. All in
interpret mode on the CPU.

- the kernel at `rep` = 1 against the XLA block-table path over batches
  that mix the predictor's empty slot (a dummy token on the trash page)
  with contexts that end mid-page, on a block edge and at the table's
  end;
- `kernels.paged_decode{kernel}`, the counter that says which kernel a
  decode program was traced with;
- the predictor's signatures, tokens and metadata, with and without a
  span program, for MHA and GQA, and the operands of each serve program;
- a bundle built with chunked prefill serves at warm start without
  compiling: builder and dispatcher signatures in lockstep; one whose
  manifest still carries a `use_ragged` key loads.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.kernels import paged_attention as pa

# 8 heads of 128 on 8 KV heads (the smallest MHA the gates admit), pages
# of 8 tokens, 8 pages a slot; blocks are cut to 2 pages (16 tokens, 128
# key columns) so that a table is four blocks
H, D, PAGE, PPS, POOL = 8, 128, 8, 8, 40
BLOCK, TABLE = 2 * PAGE, PPS * PAGE
# a predictor's empty slot: its table is all trash and the step attends
# the dummy token it has just written there, beside the one before it
EMPTY = 2
BATCHES = {
    "every-kind": [EMPTY, PAGE + 3, BLOCK, TABLE],
    "empty-slots-between": [TABLE, EMPTY, 2 * BLOCK, EMPTY, 3 * PAGE + 5],
    "empty-slots-first": [EMPTY, EMPTY, BLOCK + 1, PAGE - 1],
    "all-empty": [EMPTY] * 4,
    "all-full": [TABLE] * 3,
    "block-edges": [BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK],
}


@pytest.fixture
def interpret():
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    yield
    set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


@pytest.fixture
def decode_kernels():
    """{kernel: count} of `kernels.paged_decode`, from a clean registry."""
    from paddle_tpu.observability import metrics
    metrics.get_registry().reset()

    def read():
        m = metrics.get_registry().get("kernels.paged_decode")
        return {} if m is None else {
            x.labels.get("kernel"): x.value for x in m.samples() if x.value}
    return read


def _batch(lens, dtype, seed=0):
    """One slot a length on distinct pages in a shuffled order; an empty
    slot's table, and the rest of every table, is page 0, the trash."""
    rs = np.random.RandomState(seed)
    rnd = lambda *shape: jnp.asarray(
        rs.randn(*shape).astype(np.float32)).astype(dtype)
    q = rnd(len(lens), H, D)
    kp, vp = rnd(POOL, PAGE, H, D), rnd(POOL, PAGE, H, D)
    bt = np.zeros((len(lens), PPS), np.int32)
    free = list(rs.permutation(np.arange(1, POOL)))
    for b, n in enumerate(lens):
        if n != EMPTY:
            for j in range(-(-n // PAGE)):
                bt[b, j] = free.pop()
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_mha_kernel_matches_the_xla_block_table_path(
        monkeypatch, interpret, decode_kernels, batch, dtype):
    monkeypatch.setattr(pa, "_BLOCK_KEY_COLUMNS", 2 * PAGE * H)
    q, kp, vp, bt, lens = _batch(BATCHES[batch], dtype)
    assert pa.paged_pages_per_block(H, H, D, PAGE, q.dtype.itemsize,
                                    PPS) == 2
    out = pa.paged_attention(q, kp, vp, bt, lens)
    assert decode_kernels() == {"paged_attention": 1}
    ref = pa._paged_attention_xla(q, kp, vp, bt, lens, D ** -0.5)
    assert out.dtype == q.dtype and out.shape == q.shape
    # float32: accumulation order. bf16: P and the output are each
    # rounded to the dtype on both sides, against values up to max |v|
    atol = 2e-5 if dtype == "float32" else 2 * float(
        jnp.finfo(dtype).eps) * float(jnp.abs(vp.astype("float32")).max())
    np.testing.assert_allclose(np.asarray(out.astype("float32")),
                               np.asarray(ref.astype("float32")),
                               rtol=0, atol=atol)


@pytest.mark.parametrize("flags,want", [(True, "paged_attention"),
                                        (False, "xla")])
def test_the_counter_names_the_kernel_that_was_traced(
        interpret, decode_kernels, flags, want):
    # the fixture restores the flags
    set_flags({"use_pallas_kernels": flags, "pallas_interpret": flags})
    q, kp, vp, bt, lens = _batch(BATCHES["every-kind"], "float32")
    out = pa.paged_attention(q, kp, vp, bt, lens)
    assert decode_kernels() == {want: 1}
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(pa._paged_attention_xla(q, kp, vp, bt, lens, D ** -0.5)),
        rtol=0, atol=2e-5)


# ------------------------------------------- through the predictor --

LAYERS = 2
GEO = dict(max_batch_size=2, page_size=8, max_seq_len=64,
           enable_prefix_cache=False)
META = ((GEO["max_batch_size"] * (GEO["max_seq_len"] // GEO["page_size"]),),
        ) * len(pa.RaggedMetaBuilder.FIELDS)


def _llama(kv_heads=8):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=1024, intermediate_size=256,
        num_hidden_layers=LAYERS, num_attention_heads=8,
        num_key_value_heads=kv_heads, max_position_embeddings=128))


def _prompts(*lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 128, (n,)).tolist() for n in lengths]


def _sigs(pred, kind):
    """{operand shapes of the ragged metadata} over the signatures of
    one kind that the predictor has dispatched (the tuple's last)."""
    return {sig[-1] for sig in pred._traced_sigs if sig[0] == kind}


def _predictor(model, **kw):
    from paddle_tpu.inference import ContinuousBatchingPredictor
    return ContinuousBatchingPredictor(model, **dict(GEO, **kw))


def test_auto_decodes_mha_through_the_block_table_kernel(
        interpret, decode_kernels):
    from paddle_tpu.inference import LLMPredictor
    model = _llama()
    prompts = _prompts(5, 11, 3, 8)
    pred = _predictor(model)
    assert not pred.span_ragged
    out = pred.generate(prompts, max_new_tokens=6)
    assert _sigs(pred, "decode") == {()}
    assert decode_kernels() == {"paged_attention": LAYERS}
    assert out == LLMPredictor(model, max_batch_size=1).generate(
        prompts, max_new_tokens=6)


@pytest.mark.parametrize("kv_heads", [8, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("kernels,label", [(True, "paged_attention"),
                                           (False, "xla")])
def test_a_decode_step_is_counted_under_one_of_two_labels(
        interpret, decode_kernels, kv_heads, kernels, label):
    """One way to attend a single token over K/V pages: the block-table
    kernel, or its XLA path where there is no Pallas path. The
    ragged-grid kernel that was a third label is gone: the module's
    other two entries attend a query span."""
    set_flags({"use_pallas_kernels": kernels, "pallas_interpret": kernels})
    assert {n for n in dir(pa) if n.startswith("paged_attention")} == {
        "paged_attention", "paged_attention_varq",
        "paged_attention_ragged_varq"}
    _predictor(_llama(kv_heads)).generate(_prompts(5, 11, 3),
                                          max_new_tokens=4)
    assert decode_kernels() == {label: LAYERS}


@pytest.fixture(scope="module")
def dispatched():
    """{kind: (jitted program, its lowered input avals)} of the serve
    programs two MHA predictors with a chunk and a draft span
    dispatched, each lowered with the operands the dispatcher handed
    it; the second predictor samples on the device (its decode step is
    the sampling variant)."""
    old = get_flags(["use_pallas_kernels", "pallas_interpret"])
    set_flags({"use_pallas_kernels": True, "pallas_interpret": True})
    try:
        model, seen = _llama(), {}
        motif = _prompts(6, seed=2)[0]
        for sampling in (False, True):
            pred = _predictor(model, prefill_chunk_tokens=8,
                              spec_draft_tokens=3, sampling_enabled=sampling)
            assert pred.span_ragged
            call = pred._jit_call

            def spy(sig, fn, *args, _call=call):
                if sig[0] not in seen:
                    shapes = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(
                            np.shape(a), jnp.asarray(a).dtype), args)
                    seen[sig[0]] = (fn, jax.tree_util.tree_leaves(
                        fn.lower(*shapes).in_avals))
                return _call(sig, fn, *args)
            pred._jit_call = spy
            pred.generate(
                [motif * 4, motif[:3] * 5] + _prompts(20, 4, seed=4),
                max_new_tokens=8)
        return seen
    finally:
        set_flags({k.removeprefix("FLAGS_"): v for k, v in old.items()})


@pytest.mark.parametrize("kind,n_meta", [("decode", 0), ("decode_sample", 0),
                                         ("mixed", 6), ("spec", 6)])
def test_only_the_span_programs_take_ragged_metadata(dispatched, kind,
                                                     n_meta):
    """The inputs of each serve program as the dispatcher hands them,
    lowered: the six `[B * pages_per_seq]` int32 arrays on the mixed and
    verify programs, none on the two decode programs, whose functions
    have no operands past their named ones."""
    import inspect
    fn, avals = dispatched[kind]
    assert sum(a.shape == META[0] and a.dtype == jnp.int32
               for a in avals) == n_meta
    raw = inspect.unwrap(fn)
    assert any(p.kind is p.VAR_POSITIONAL for p in
               inspect.signature(raw).parameters.values()) == bool(n_meta)


@pytest.mark.parametrize("value", ["auto", True, False])
def test_the_use_ragged_keyword_is_ignored(interpret, value):
    """`benchmarks/rehearse.py` still passes the keyword and the runners
    still read the attribute: whatever is passed, the span programs get
    the metadata by the gate alone and the decode step is the one
    program (its text carries no locations)."""
    model = _llama()
    want = _predictor(model, prefill_chunk_tokens=8)
    pred = _predictor(model, prefill_chunk_tokens=8, use_ragged=value)
    assert pred.use_ragged is False and want.use_ragged is False
    assert pred.span_ragged and want.span_ragged
    assert pred.lower_decode_step().as_text() \
        == want.lower_decode_step().as_text()
    assert not _predictor(model, use_ragged=value).span_ragged


def _count_varq_kernel(monkeypatch):
    calls = []
    real = pa._paged_attention_ragged_varq_pallas

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)
    monkeypatch.setattr(pa, "_paged_attention_ragged_varq_pallas", spy)
    return calls


def test_chunked_prefill_keeps_the_varq_kernel_and_its_metadata(
        monkeypatch, interpret, decode_kernels):
    model = _llama()
    prompts = _prompts(20, 4, seed=4)
    want = _predictor(model).generate(prompts, max_new_tokens=4)
    calls = _count_varq_kernel(monkeypatch)
    decode_kernels()
    pred = _predictor(model, prefill_chunk_tokens=8)
    assert pred.span_ragged
    assert pred.generate(prompts, max_new_tokens=4) == want
    assert pred.stats["chunked_requests"] == 1
    assert pred.stats["mixed_steps"] >= 2
    # the mixed step carries the metadata and rides the varq kernel,
    # once a layer of each span bucket; the decode step carries none
    # and rides the block-table kernel
    assert _sigs(pred, "mixed") == {META}
    assert calls and len(calls) % LAYERS == 0
    assert _sigs(pred, "decode") == {()}
    assert decode_kernels().keys() == {"paged_attention"}


def test_speculative_verify_keeps_the_varq_kernel_and_its_metadata(
        monkeypatch, interpret):
    model = _llama()
    motif = _prompts(6, seed=2)[0]
    prompts = [motif * 4, motif[:3] * 5]
    want = _predictor(model).generate(prompts, max_new_tokens=8)
    calls = _count_varq_kernel(monkeypatch)
    pred = _predictor(model, spec_draft_tokens=3)
    assert pred.span_ragged
    assert pred.generate(prompts, max_new_tokens=8) == want
    assert pred.stats["spec_ticks"] >= 1
    assert _sigs(pred, "spec") == {META}
    assert calls and {s[1] for s in calls} == {4}
    # a tick without drafts falls back to the plain decode program
    assert _sigs(pred, "decode") <= {()}


@pytest.mark.parametrize("chunk", [0, 8], ids=["plain", "chunked"])
def test_a_gqa_predictor_has_no_metadata_anywhere(
        monkeypatch, interpret, decode_kernels, chunk):
    """GQA fails the varq gate: the span programs attend through the
    XLA varq path, without metadata, as before; decode as before."""
    model = _llama(kv_heads=2)
    prompts = _prompts(20, 4, seed=4)
    calls = _count_varq_kernel(monkeypatch)
    pred = _predictor(model, prefill_chunk_tokens=chunk)
    assert not pred.span_ragged
    out = pred.generate(prompts, max_new_tokens=4)
    assert _sigs(pred, "decode") == {()}
    assert _sigs(pred, "mixed") == ({()} if chunk else set())
    assert not calls
    assert decode_kernels().keys() == {"paged_attention"}
    set_flags({"use_pallas_kernels": False, "pallas_interpret": False})
    assert _predictor(model).generate(prompts, max_new_tokens=4) == out


def test_without_a_pallas_path_there_is_no_metadata(decode_kernels):
    pred = _predictor(_llama(), prefill_chunk_tokens=8)
    assert not pred.span_ragged
    pred.generate(_prompts(20, 4, seed=4), max_new_tokens=2)
    assert _sigs(pred, "mixed") == {()} and _sigs(pred, "decode") == {()}
    assert decode_kernels().keys() == {"xla"}


@pytest.mark.parametrize("max_seq_len,prompt_len", [(18, 17), (64, 33)],
                         ids=["tight-direct", "roomy-steered"])
def test_auto_bundle_with_chunked_prefill_serves_zero_compile(
        interpret, tmp_path, max_seq_len, prompt_len):
    """tests/test_mixed_step.py's zero-compile case on an MHA model the
    gates admit: the builder gives the mixed buckets the metadata and
    the decode step none, as the dispatcher does, however a bucket was
    captured."""
    from paddle_tpu.inference import aot
    model = _llama()
    geo = dict(GEO, max_seq_len=max_seq_len, prefill_chunk_tokens=16)
    d = str(tmp_path / "engine")
    manifest = aot.build_engine(model, d, prompt_buckets=(8,),
                                batch_sizes=(1,), max_new_tokens=2,
                                wire_cache=False, **geo)
    kinds = [rec.get("kind") for rec in manifest["artifacts"].values()]
    assert kinds.count("mixed") == 2 and kinds.count("decode") == 1
    pred, eng = aot.warm_start(model, d, wire_cache=False)
    assert pred.span_ragged
    # a chunked prompt through the mixed buckets, then a short one
    # through the decode step
    long, short = _prompts(prompt_len, 5, seed=7)
    out = pred.generate([long], max_new_tokens=1) \
        + pred.generate([short], max_new_tokens=2)
    ref = _predictor(model, **geo)
    assert out == ref.generate([long], max_new_tokens=1) \
        + ref.generate([short], max_new_tokens=2)
    assert pred.stats["chunked_requests"] == 1
    assert pred.stats["decode_steps"] > pred.stats["mixed_steps"]
    assert eng.stats["misses"] == 0, eng.stats


def test_a_manifest_with_a_use_ragged_key_warm_starts(interpret, tmp_path):
    """A bundle written while `use_ragged` was a compiled-geometry key
    has it in its manifest. It is ignored on read: the constructor
    takes the keyword and drops it, and a request that disagrees with
    the manifest's value no longer invalidates the bundle."""
    from paddle_tpu.inference import aot
    model = _llama()
    d = str(tmp_path / "engine")
    manifest = aot.build_engine(model, d, prompt_buckets=(8,),
                                batch_sizes=(1,), max_new_tokens=2,
                                wire_cache=False, use_ragged=True, **GEO)
    assert manifest["geometry"]["use_ragged"] is True
    pred, eng = aot.warm_start(model, d, wire_cache=False, strict=True,
                               use_ragged=False)
    assert pred.use_ragged is False
    prompt = _prompts(5, seed=7)
    assert pred.generate(prompt, max_new_tokens=2) \
        == _predictor(model).generate(prompt, max_new_tokens=2)
    assert eng.stats["misses"] == 0, eng.stats
