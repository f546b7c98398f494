"""The serve loop's tick by stage (observability.tracing.tick), JAX's
compile events as a log (observability.runtime), the page-utilisation
count that replaced the trie walk, and the flight recorder's index by
trace. Counts and structure only: no rates, no times compared.
"""
import glob
import json
import os
import random
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.observability import runtime as obsrt
from paddle_tpu.observability import tracing as tr

TOP_STAGES = {"serve.intake", "serve.admit", "serve.gauges",
              "serve.dispatch", "serve.resolve", "serve.emit"}
NESTED = {"serve.prefill": "serve.admit",
          "serve.resolve.wait": "serve.resolve"}
ALL_STAGES = TOP_STAGES | set(NESTED)


@pytest.fixture(autouse=True)
def _clean():
    obs.enabled(True)
    tr.clear_ticks()
    tr.flight_recorder().clear()
    yield
    obs.enabled(True)
    tr.clear_ticks()
    tr.flight_recorder().clear()


def _model(**kw):
    paddle.seed(0)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def _predictor(model=None, **kw):
    from paddle_tpu.inference import ContinuousBatchingPredictor
    geometry = dict(max_batch_size=2, page_size=8, max_seq_len=64)
    geometry.update(kw)
    return ContinuousBatchingPredictor(model or _model(), **geometry)


def _prompts(seed=0, lens=(5, 11, 3, 9)):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 256, (n,)).tolist() for n in lens]


# ------------------------------------------------------------------ ticks --
class TestTicks:
    def test_stage_names_and_sums(self):
        cb = _predictor(name="r0")
        out = cb.generate(_prompts(), max_new_tokens=6)
        assert all(len(o) == 6 for o in out)
        ticks = tr.ticks()
        assert ticks and all(t["name"] == "serve.tick" for t in ticks)
        assert {t["replica"] for t in ticks} == {"r0"}
        seen = set()
        for t in ticks:
            st = t["stages"]
            assert set(st) <= ALL_STAGES
            seen |= set(st)
            top = sum(s for n, s in st.items() if n in TOP_STAGES)
            assert top <= t["dur"] + 1e-9
            for child, parent in NESTED.items():
                if child in st:
                    assert st[child] <= st[parent] + 1e-9
        assert seen == ALL_STAGES       # the table's names, all of them
        assert sum(t["admitted"] for t in ticks) == 4
        assert sum(1 for t in ticks if t["prefill"]) >= 2   # 2 slots
        assert max(t["active"] for t in ticks) == 2
        # ticks follow each other on one clock
        starts = [t["t0"] for t in ticks]
        assert starts == sorted(starts)

    def test_window_cut(self):
        cb = _predictor()
        cb.generate(_prompts(), max_new_tokens=4)
        ticks = tr.ticks()
        mid = ticks[len(ticks) // 2]["t0"]
        early, late = tr.ticks(until=mid), tr.ticks(since=mid)
        assert len(early) + len(late) == len(ticks)
        assert all(t["t0"] < mid for t in early)
        assert all(t["t0"] >= mid for t in late)

    def test_disabled_records_nothing_same_tokens(self):
        model = _model()
        prompts = _prompts(3)
        ref = _predictor(model).generate(prompts, max_new_tokens=6)
        tr.clear_ticks()
        n_log = len(obsrt.compile_log())
        obs.enabled(False)
        try:
            got = _predictor(model).generate(prompts, max_new_tokens=6)
        finally:
            obs.enabled(True)
        assert got == ref
        assert tr.ticks() == []
        assert len(obsrt.compile_log()) == n_log
        assert tr.tick("serve.tick") is not tr.NULL_TICK
        obs.enabled(False)
        try:
            assert tr.tick("serve.tick") is tr.NULL_TICK
            assert tr.NULL_TICK.stage("serve.admit") is tr.NULL_SPAN
        finally:
            obs.enabled(True)

    def test_closed_generator_leaves_no_open_tick(self):
        """A consumer that abandons the raw generator inside
        `serve.emit` still gets a finished record, and the predictor's
        current tick is the no-op again."""
        cb = _predictor()
        from paddle_tpu.serving.streaming import ServeRequest
        reqs = [ServeRequest(p, 6) for p in _prompts()]
        results, status = [], []
        gen = cb._serve(reqs, None, results, status, set(), None, 6)
        next(gen)                  # suspended at a yield inside emit
        assert cb._tick is not tr.NULL_TICK
        gen.close()
        assert cb._tick is tr.NULL_TICK
        last = tr.ticks()[-1]
        assert "serve.emit" in last["stages"] and last["dur"] > 0
        assert set(status) == {"cancelled"}

    def test_page_utilisation_gauge_every_tick_equals_walk(self):
        """The gauge is set on every pass from the pool's own count,
        which is what the trie walk finds."""
        cb = _predictor(name="g0")
        cb.generate(_prompts(5, (17, 9, 12)), max_new_tokens=3)
        pool, cache = cb.pool, cb.prefix_cache
        walk = cache.reclaimable_count(pool)
        assert walk > 0
        assert pool.free_count == len(pool._free) + walk
        util = obs.gauge("serving.page_utilization").value(replica="g0")
        assert util == pytest.approx(
            (cb.capacity - pool.free_count) / cb.capacity)

    def test_flight_dump_carries_ticks(self, tmp_path):
        cb = _predictor()
        cb.generate(_prompts(), max_new_tokens=3)
        p = tr.flight_dump(path=str(tmp_path / "f.json"), reason="unit")
        doc = json.load(open(p))
        assert doc["ticks"] and doc["ticks"][-1]["name"] == "serve.tick"
        assert len(doc["ticks"]) <= tr._DUMP_TICKS
        assert set(doc["ticks"][-1]["stages"]) <= ALL_STAGES

    def test_ring_is_bounded(self):
        assert tr._ticks.maxlen == tr._TICK_CAPACITY == 65536
        for _ in range(10):
            with tr.tick("t") as t:
                t.note(k=1)
        assert len(tr.ticks()) == 10 and tr.ticks()[0]["k"] == 1

    def test_stage_entered_twice_sums(self):
        with tr.tick("t") as t:
            with t.stage("t.a"):
                pass
            first = t._rec["t.a"]
            with t.stage("t.a"):
                pass
        assert tr.ticks()[-1]["stages"]["t.a"] >= first

    def test_tick_closes_on_exception(self):
        with pytest.raises(ValueError):
            with tr.tick("t") as t:
                with t.stage("t.a"):
                    raise ValueError("x")
        rec = tr.ticks()[-1]
        assert rec["name"] == "t" and "t.a" in rec["stages"]

    def test_ring_records_stay_off_the_collectors_books(self):
        """A record is one flat dict of strings and numbers, which
        CPython does not track: 65536 of them cost a full collection
        nothing (a nested dict a tick brought one into the window)."""
        import gc
        cb = _predictor(name="r1")
        cb.generate(_prompts(), max_new_tokens=4)
        assert len(tr._ticks) > 4
        for rec in tr._ticks:
            assert not gc.is_tracked(rec)
            assert all(isinstance(v, (str, int, float, bool))
                       for v in rec.values())
        got = tr.ticks()[-1]
        assert set(got) == {"name", "replica", "t0", "dur", "stages",
                            "active", "admitted", "prefill"}


def _host_lines(trace_dir):
    import jax
    lines = []
    for pb in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True):
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            for line in plane.lines:
                evs = [(ev.name.split("#")[0], ev.start_ns,
                        ev.start_ns + ev.duration_ns, ev.name,
                        dict(ev.stats))
                       for ev in line.events]
                if any(n == "serve.tick" for n, *_ in evs):
                    lines.append(evs)
    return lines


def test_annotations_on_the_profilers_clock(tmp_path):
    """Under a profiler session the stages are TraceAnnotations on the
    serve thread: every stage inside a `serve.tick`, `serve.prefill`
    inside `serve.admit`, the prefill's arguments beside it."""
    import jax
    cb = _predictor()
    cb.generate(_prompts(), max_new_tokens=2)          # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        cb.generate(_prompts(7), max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    lines = _host_lines(str(tmp_path))
    assert len(lines) == 1, "one serve thread"
    evs = lines[0]
    by_name = {}
    for n, s, e, full, stats in evs:
        by_name.setdefault(n, []).append((s, e, full, stats))
    assert ALL_STAGES | {"serve.tick"} <= set(by_name)

    def inside(child, parent):
        return all(any(ps <= s and e <= pe for ps, pe, *_ in
                       by_name[parent]) for s, e, *_ in by_name[child])

    for name in ALL_STAGES:
        assert inside(name, "serve.tick"), name
    assert inside("serve.prefill", "serve.admit")
    assert inside("serve.resolve.wait", "serve.resolve")
    _, _, full, stats = by_name["serve.prefill"][0]
    carried = full + " " + " ".join(f"{k}={v}" for k, v in stats.items())
    for key in ("n=", "bucket=", "traces="):
        assert key in carried


# ------------------------------------------------------------ compile log --
class TestCompileLog:
    def test_new_prefill_shape_after_warm_up_is_logged_with_its_sig(self):
        cb = _predictor()
        cb.generate(_prompts(0, (5, 6)), max_new_tokens=3)   # bucket 8
        warm = set(cb._traced_sigs)
        before = obsrt.compile_log()
        t_before = before[-1]["t"] if before else 0.0
        traces0 = obs.counter("jit.traces").value()
        cb.generate(_prompts(1, (5, 6)), max_new_tokens=3)   # same shapes
        assert [e for e in obsrt.compile_log(since=t_before + 1e-9)
                if e["sig"] and e["sig"].startswith("('prefill'")] == []
        mark = obsrt.compile_log()[-1]["t"] if obsrt.compile_log() \
            else 0.0
        cb.generate(_prompts(2, (30,)), max_new_tokens=3)    # bucket 32
        (new_sig,) = [s for s in cb._traced_sigs - warm
                      if s[0] == "prefill"]
        late = obsrt.compile_log(since=mark + 1e-9)
        mine = [e for e in late if e["sig"] == str(new_sig)]
        assert [e["kind"] for e in mine].count("compile") == 1
        assert any(e["kind"] == "trace" for e in mine)
        assert all(e["seconds"] >= 0 for e in mine)
        assert obs.counter("jit.traces").value() > traces0
        assert obs.counter("jit.trace_seconds").value() > 0
        assert obs.counter("jit.compile_seconds").value() > 0

    def test_listener_registered_once(self):
        from jax._src import monitoring as mon
        obsrt.watch_compiles()
        obsrt.watch_compiles()
        durs = mon.get_event_duration_listeners()
        assert durs.count(obsrt._on_compile_event) == 1
        assert mon.get_event_listeners().count(
            obsrt._on_compile_event) == 1

    @pytest.mark.parametrize("event,kind,counter", [
        ("/jax/core/compile/jaxpr_trace_duration", "trace", "jit.traces"),
        ("/jax/core/compile/backend_compile_duration", "compile",
         "jit.compile_seconds"),
        ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower",
         "jit.compile_seconds"),
        ("/jax/compilation_cache/cache_hits", "cache_hit",
         "jit.cache_hits"),
        ("/jax/compilation_cache/cache_misses", "cache_miss",
         "jit.cache_misses"),
    ])
    def test_event_kinds(self, event, kind, counter):
        c0 = obs.counter(counter).value()
        with obsrt.jit_tag(("decode", (2, 8))):
            if kind in ("cache_hit", "cache_miss"):
                obsrt._on_compile_event(event)
            else:
                obsrt._on_compile_event(event, 0.25, fun_name="f")
        e = obsrt.compile_log()[-1]
        assert e["kind"] == kind and e["sig"] == "('decode', (2, 8))"
        assert obs.counter(counter).value() > c0
        assert obsrt._jit_tag.sig is None

    def test_unknown_event_ignored_and_tags_nest(self):
        n = len(obsrt.compile_log())
        obsrt._on_compile_event("/jax/some/other_event", 1.0)
        assert len(obsrt.compile_log()) == n
        with obsrt.jit_tag("outer"):
            with obsrt.jit_tag("inner"):
                assert obsrt._jit_tag.sig == "inner"
            assert obsrt._jit_tag.sig == "outer"

    def test_tag_is_per_thread(self):
        seen = []
        with obsrt.jit_tag("main"):
            th = threading.Thread(
                target=lambda: seen.append(obsrt._jit_tag.sig))
            th.start()
            th.join(timeout=10)
        assert seen == [None]


# ------------------------------------------------- O(1) reclaimable count --
def _pool(n_pages=24, page=4):
    from paddle_tpu.generation.kv_cache import PagedKVPool, PrefixCache
    pool = PagedKVPool(1, n_pages, page, 1, 2)
    cache = PrefixCache(page)
    pool.reclaimer = cache
    return pool, cache


def _droppable_by_walk(cache, pool):
    """What `PrefixCache._droppable` has to find, by walking the whole
    trie (the way it was found before the trie kept its tips)."""
    out = []

    def walk(node):
        for toks, rec in node.partials.items():
            if pool.ref_count(rec[0]) == 1:
                out.append((rec[2], "partial", node, toks))
        for chunk, child in node.children.items():
            if (not child.children and not child.partials
                    and pool.ref_count(child.page) == 1):
                out.append((child.last_use, "leaf", node, chunk))
            else:
                walk(child)

    walk(cache._root)
    return out


@pytest.mark.parametrize("seed", range(8))
def test_reclaimable_count_equals_walk(seed):
    """admit / share / evict / reclaim / import_span in a random order:
    after every step the pool's count is the trie walk's."""
    from paddle_tpu.generation.kv_cache import PagedKVPool
    rng = random.Random(seed)
    page = 4
    pool, cache = _pool(24, page)
    donor = PagedKVPool(1, 8, page, 1, 2)
    stems = [[rng.randrange(2, 9) for _ in range(page * rng.randint(1, 3))]
             for _ in range(3)]
    held = []                              # page lists requests hold

    def check():
        walk = cache.reclaimable_count(pool)
        assert pool._reclaimable == walk
        assert pool.free_count == len(pool._free) + walk
        assert pool._reclaimable >= 0
        for p, n in pool._cache_held.items():
            assert 1 <= n <= pool.ref_count(p)
        # the tips the trie keeps are the tips a walk finds
        assert sorted(cache._droppable(pool), key=lambda c: c[0]) == \
            sorted(_droppable_by_walk(cache, pool), key=lambda c: c[0])

    def prompt():
        stem = rng.choice(stems)
        tail = [rng.randrange(2, 9) for _ in range(rng.randint(0, 6))]
        return stem + tail

    def admit():
        p = prompt()
        pages, covered, partial, _ = cache.lookup(p)
        shared = pages + ([partial[0]] if partial else [])
        pool.retain(shared)
        need = -(-(len(p) + 2) // page)
        fresh = pool.alloc(need - len(pages))
        if fresh is None:
            pool.release(shared)
            return
        if partial is not None:
            pool.copy_into(partial[0], fresh[0])
            pool.release([partial[0]])
        mine = pages + fresh
        cache.insert(p, mine[:-(-len(p) // page)], None, pool)
        held.append(mine)

    def evict():
        if held:
            pool.release(held.pop(rng.randrange(len(held))))

    def reclaim():
        cache.reclaim(pool, rng.randint(1, 4))

    def import_span():
        p = prompt()
        n = -(-len(p) // page)
        ids = donor.alloc(n)
        span = donor.export_span(p, ids, next_token=3)
        donor.release(ids)
        try:
            pool.import_span(span, prefix_cache=cache)
        except MemoryError:
            pass

    steps = [admit, admit, evict, reclaim, import_span]
    for _ in range(60):
        rng.choice(steps)()
        check()
    while held:
        evict()
        check()
    cache.clear(pool)
    check()
    assert pool._reclaimable == 0 and pool._cache_held == {}
    assert cache._tips == set() and cache._with_partials == set()
    assert pool.free_count == pool.num_pages


def test_page_held_twice_by_the_trie_is_not_reclaimable_until_one_drop():
    pool, cache = _pool(4, 4)
    (p,) = pool.alloc(1)
    pool.cache_hold(p)
    pool.cache_hold(p)
    pool.release([p])                     # the request lets go
    assert pool.ref_count(p) == 2 and pool._reclaimable == 0
    pool.cache_drop(p)
    assert pool.ref_count(p) == 1 and pool._reclaimable == 1
    pool.cache_drop(p)
    assert pool.ref_count(p) == 0 and pool._reclaimable == 0
    assert pool.free_count == 4


def test_free_count_without_a_reclaimer_counts_only_the_free_list():
    from paddle_tpu.generation.kv_cache import PagedKVPool, PrefixCache
    pool = PagedKVPool(1, 4, 4, 1, 2)
    cache = PrefixCache(4)
    ids = pool.alloc(1)
    cache.insert([1, 2, 3, 4], ids, None, pool)
    pool.release(ids)
    assert pool._reclaimable == 1 and pool.free_count == 3
    pool.reclaimer = cache
    assert pool.free_count == 4


# ------------------------------------------------------------- spans_of --
class TestSpansOf:
    def test_equals_filtering_spans(self):
        roots = [tr.start_span("root", parent=None, i=i) for i in range(4)]
        for k in range(3):
            for r in roots:
                tr.start_span("child", parent=r, k=k).end()
        for r in roots:
            r.end()
        rec = tr.flight_recorder()
        for r in roots:
            want = [s for s in rec.spans() if s["trace"] == r.trace_id]
            assert rec.spans_of(r.trace_id) == want and len(want) == 4
        assert rec.spans_of("no-such-trace") == []

    @pytest.mark.parametrize("capacity", [1, 3, 8])
    def test_forgets_a_trace_with_its_last_span(self, capacity):
        rec = tr.FlightRecorder(capacity=capacity)
        old, tr._recorder = tr._recorder, rec
        try:
            ids = []
            for i in range(20):
                root = tr.start_span("root", parent=None, i=i)
                tr.start_span("child", parent=root).end()
                root.end()
                ids.append(root.trace_id)
                ring = rec.spans()
                assert len(ring) == min(capacity, 2 * (i + 1))
                assert set(rec._by_trace) == {s["trace"] for s in ring}
                for tid in ids:
                    assert rec.spans_of(tid) == \
                        [s for s in ring if s["trace"] == tid]
        finally:
            tr._recorder = old
        assert rec.spans_of(ids[0]) == []
        rec.clear()
        assert rec._by_trace == {} and rec.spans() == []

    def test_router_stage_histogram_reads_the_index(self, monkeypatch):
        """`_observe_stages` exports serve.request.stage.seconds from
        one lookup: copying the ring is not on a request's path."""
        from paddle_tpu.serving import Router
        rec = tr.flight_recorder()
        copies = []
        orig = rec.spans
        monkeypatch.setattr(rec, "spans",
                            lambda: copies.append(1) or orig())
        router = Router([_predictor()])
        try:
            hs = [router.submit(p, max_new_tokens=3) for p in _prompts()]
            for h in hs:
                assert len(h.result(timeout=120)) == 3
        finally:
            router.shutdown(timeout=60.0)
        assert copies == []
        hist = obs.histogram("serve.request.stage.seconds")
        stages = {s.labels.get("stage") for s in hist.samples()}
        assert {"queue", "prefill", "decode"} <= stages
