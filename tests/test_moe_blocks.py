"""The dropless expert layer's token pieces (`moe/dropless.py`
`block_tokens`, `_moe_in_blocks`):

- a token's result and the routing counts do not depend on the piece
  that held the token: 1, 2 and 4 pieces against the whole, under both
  routing rules, with `valid` marking left padding;
- the rule's own table: the pieces the three served geometries get, a
  program at or under its piece, a decode step, tokens the piece does
  not divide;
- `moe.prefill_blocks{tokens}`: counted where a program is traced, by
  the pieces its tokens were cut into, and not when it runs again.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.observability import metrics

F32 = jnp.float32
# 2 prompts of 32 positions, 16 experts in 4 groups of which this chip
# holds the first two groups, top-4
ROWS, BUCKET, HIDDEN, WIDTH, EXPERTS, HELD, TOP_K = 2, 32, 32, 12, 16, 8, 4


def _layer(seed=7):
    rng = np.random.default_rng(seed)
    draw = lambda scale, *s: jnp.asarray(rng.normal(size=s) * scale, F32)
    x = draw(1.0, ROWS, BUCKET, HIDDEN)
    # left padding: the first 5 and 11 positions of the two rows
    valid = jnp.asarray(np.arange(BUCKET)[None, :] >= np.array([[5], [11]]))
    return (x, valid, draw(0.3, HIDDEN, EXPERTS),
            draw(0.2, HELD, HIDDEN, 2 * WIDTH), draw(0.2, HELD, WIDTH, HIDDEN),
            draw(0.05, EXPERTS))


def _route(name, bias):
    if name == "softmax_topk":
        return None
    return lambda logits: dropless.group_limited_sigmoid_route(
        logits, bias, TOP_K, 4, 2, 2.5)


@pytest.mark.parametrize("rule", ["softmax_topk", "group_limited_sigmoid"])
@pytest.mark.parametrize("pieces", [1, 2, 4])
def test_pieces_give_what_the_whole_gives(rule, pieces):
    x, valid, rw, wi, wo, bias = _layer()
    n = ROWS * BUCKET
    kw = dict(held=tuple(range(HELD)), top_k=TOP_K, route=_route(rule, bias))
    # the whole: one call of the piece's own function on every token
    local_of = jnp.asarray(np.where(np.arange(EXPERTS) < HELD,
                                    np.arange(EXPERTS), HELD), jnp.int32)
    want_y, want_c = dropless._route_block(
        x.reshape(n, HIDDEN), valid.reshape(n), rw, wi, wo, local_of, HELD,
        TOP_K, kw["route"])
    y, counts = dropless._moe_in_blocks(x, valid, rw, wi, wo, **kw,
                                        block=n // pieces)
    assert y.shape == x.shape and counts.shape == (2 + HELD,)
    assert (np.asarray(counts) == np.asarray(want_c)).all()
    # padding is computed and not counted
    assert int(counts[0]) == (n - 16) * TOP_K
    assert 0 < int(counts[1]) < int(counts[0])
    assert int(counts[2:].sum()) == int(counts[1])
    scale = np.abs(np.asarray(want_y)).max()
    assert np.abs(np.asarray(y).reshape(n, HIDDEN)
                  - np.asarray(want_y)).max() < 1e-6 * scale
    # and the layer's own entry, whose rule makes these 64 tokens one
    # piece, agrees
    y0, c0 = dropless.dropless_moe(x, valid, rw, wi, wo, **kw)
    assert (np.asarray(c0) == np.asarray(want_c)).all()
    assert np.abs(np.asarray(y0) - np.asarray(y)).max() < 1e-6 * scale


# (hidden, experts, held, top_k) of the three served geometries; every
# expert is 768 wide, activations and banks bfloat16
GRANITE, KEYE, LING = (4096, 72, 36, 10), (2048, 128, 16, 8), \
    (2560, 512, 128, 8)


def _piece(tokens, geometry, width=768, itemsize=2):
    hidden, experts, held, top_k = geometry
    return dropless.block_tokens(
        tokens, top_k, experts, hidden * itemsize,
        held * 3 * hidden * width * itemsize)


@pytest.mark.parametrize("tokens,geometry,block", [
    # the largest prefill of the three served geometries: an expert of
    # Granite's expects the ridge at 2048 tokens, Keye's 16 banks are
    # fewer bytes than the rows of 2048 tokens, Ling's 128 banks more
    # than the rows of 8192
    pytest.param(8 * 1024, GRANITE, 2048, id="granite-8x1024"),
    pytest.param(2 * 16384, KEYE, 2048, id="keye-2x16384"),
    pytest.param(2 * 16384, LING, 16384, id="ling-2x16384"),
    # every other program of cell 5 is one piece
    pytest.param(16384, LING, 16384, id="ling-1x16384"),
    pytest.param(2 * 4096, LING, 8192, id="ling-2x4096"),
    pytest.param(4096, LING, 4096, id="ling-1x4096"),
    # at or under the smallest piece: one piece, whatever the router
    pytest.param(2048, GRANITE, 2048, id="granite-2x1024"),
    pytest.param(640, GRANITE, 640, id="granite-640"),
    pytest.param(2048, KEYE, 2048, id="keye-2048"),
    # a decode step
    pytest.param(32, LING, 32, id="decode-32"),
    pytest.param(64, GRANITE, 64, id="decode-64"),
    # any multiple of the piece is cut; tokens the piece does not divide
    # are one piece, as they were
    pytest.param(3 * 16384, LING, 16384, id="ling-3x16384"),
    pytest.param(3 * 8192, LING, 3 * 8192, id="ling-3x8192"),
    pytest.param(5000, GRANITE, 5000, id="granite-5000"),
    # all 128 of Keye's experts on one chip: eight times the banks, and
    # the ridge at 4096
    pytest.param(2 * 16384, (2048, 128, 128, 8), 4096, id="keye-all-held"),
    # all 512 of Ling's: the ridge still stops it at 16384
    pytest.param(4 * 16384, (2560, 512, 512, 8), 16384, id="ling-all-held"),
])
def test_the_rules_table(tokens, geometry, block):
    got = _piece(tokens, geometry)
    assert got == block
    assert tokens % got == 0


def _blocks_counted():
    m = metrics.get_registry().get("moe.prefill_blocks")
    return {} if m is None else {
        s.labels.get("tokens"): s.value for s in m.samples() if s.value}


def test_pieces_are_counted_where_a_program_is_traced(monkeypatch):
    """Two programs: one of 4 pieces, one of 1. Each counts once, when
    it is traced; running them again counts nothing."""
    metrics.get_registry().reset()
    # the rule at toy sizes: pieces of 16 tokens
    monkeypatch.setattr(dropless, "MIN_BLOCK_TOKENS", 16)
    monkeypatch.setattr(dropless, "RIDGE_ROWS", 2)
    x, valid, rw, wi, wo, _ = _layer()
    assert _piece(ROWS * BUCKET, (HIDDEN, EXPERTS, HELD, TOP_K), WIDTH,
                  4) == 16
    kw = dict(held=tuple(range(HELD)), top_k=TOP_K)
    prefill = jax.jit(lambda *a: dropless.dropless_moe(*a, **kw))
    step = jax.jit(lambda *a: dropless.dropless_moe(*a, **kw))
    prefill(x, valid, rw, wi, wo)
    assert _blocks_counted() == {"16": 4}
    step(x[:, :4], valid[:, :4], rw, wi, wo)
    assert _blocks_counted() == {"16": 4, "8": 1}
    for _ in range(3):
        y, counts = prefill(x, valid, rw, wi, wo)
        step(x[:, :4], valid[:, :4], rw, wi, wo)
    assert _blocks_counted() == {"16": 4, "8": 1}
    # the counted pieces are the ones that ran
    assert int(counts[0]) == (ROWS * BUCKET - 16) * TOP_K
