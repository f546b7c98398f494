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
PF_FIELDS = {"pf_n", "pf_tokens", "pf_padded", "pf_s", "pf_stalled"}


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

    def test_idle_slot_steps_are_the_slots_less_the_active_of_each_step(
            self, monkeypatch):
        """`serving.idle_slot_steps`: the slots that rode a dispatched
        decode step with no request, and `tools/serve_account.py` prints
        it beside the steps."""
        import importlib.util
        from paddle_tpu.inference import ContinuousBatchingPredictor
        rode = []
        real = ContinuousBatchingPredictor._dispatch_step

        def spy(self, active, *args, **kw):
            rode.append(len(active))
            return real(self, active, *args, **kw)

        monkeypatch.setattr(ContinuousBatchingPredictor, "_dispatch_step",
                            spy)
        read = lambda name: obs.counter(name).value(replica="idle0")
        cb = _predictor(max_batch_size=4, name="idle0")
        assert read("serving.idle_slot_steps") == 0
        # five requests over four slots: the fifth decodes alone at the end
        cb.generate(_prompts(lens=(5, 11, 3, 9, 7)), max_new_tokens=5)
        assert len(rode) == cb.stats["decode_steps"] \
            == read("serving.decode_steps")
        assert 1 in rode and 4 in rode
        assert read("serving.idle_slot_steps") == sum(4 - n for n in rode) > 0
        # a step is dispatched on a tick that has active slots, not on all
        assert read("serving.idle_slot_steps") <= sum(
            4 - t["active"] for t in tr.ticks() if t["active"])
        spec = importlib.util.spec_from_file_location(
            "serve_account", os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
                "tools", "serve_account.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        total = lambda name: sum(
            s.value for s in obs.counter(name).samples())
        assert tool.decode_steps() == {
            "steps": total("serving.decode_steps"),
            "idle_slot_steps": total("serving.idle_slot_steps")}
        assert tool.decode_steps()["idle_slot_steps"] >= sum(
            4 - n for n in rode)

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
        got = tr.ticks()[-1]              # the pass that left the loop
        assert set(got) == {"name", "replica", "t0", "dur", "stages",
                            "active", "admitted", "prefill"}
        assert set(tr.ticks()[0]) == set(got) | {"tokens", "first"} \
            | PF_FIELDS


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
    for key in ("n=", "bucket=", "traces=", "kind=batch", "rows=",
                "tokens=", "padded=", "stalled="):
        assert key in carried


# ------------------------------------------------- the prefill account --
def _sum(ticks, field):
    return sum(t.get(field, 0) for t in ticks)


def _stream(cb, prompts, max_new):
    """Serve `prompts` with a budget each; the tokens every stream
    received, by request."""
    from paddle_tpu.serving.streaming import ServeRequest
    reqs = [ServeRequest(p, n) for p, n in zip(prompts, max_new)]
    got = {}
    for ev in cb._serve(reqs, None, [], [], set(), None, max(max_new)):
        if ev.kind == "token":
            got.setdefault(ev.request, []).extend(ev.span or (ev.token,))
    return got


class TestPrefillAccount:
    def test_forwarded_padded_and_prompts_of_known_lengths(self):
        """Two rounds of two prompts: each round's misses go into one
        program a bucket, power-of-two rows of it."""
        cb = _predictor()
        lens = (5, 11, 3, 9)             # buckets 8, 16, 8, 16
        cb.generate(_prompts(0, lens), max_new_tokens=4)
        ticks = tr.ticks()
        assert _sum(ticks, "pf_n") == 4
        assert _sum(ticks, "pf_tokens") == sum(lens)
        assert _sum(ticks, "pf_padded") == 8 + 16 + 8 + 16
        assert cb.stats["prefill_batches"] == 4
        with_pf = [t for t in ticks if "pf_s" in t]
        assert len(with_pf) == 2 and all(t["prefill"] for t in with_pf)
        assert all(t["pf_s"] > 0 and t["pf_s"] <= t["stages"][
            "serve.prefill"] for t in with_pf)
        assert all(PF_FIELDS <= set(t) for t in with_pf)
        assert not any(PF_FIELDS & set(t) for t in ticks
                       if t not in with_pf)

    def test_one_program_takes_a_round_of_one_bucket(self):
        cb = _predictor(max_batch_size=4)
        cb.generate(_prompts(1, (9, 12, 15)), max_new_tokens=2)
        ticks = tr.ticks()
        assert _sum(ticks, "pf_n") == 3
        assert _sum(ticks, "pf_tokens") == 36
        assert _sum(ticks, "pf_padded") == 4 * 16    # 3 prompts: 4 rows
        assert cb.stats["prefill_batches"] == 1

    def test_tokens_of_the_ticks_are_the_tokens_the_streams_received(self):
        cb = _predictor()
        max_new = [7, 2, 5, 3]
        got = _stream(cb, _prompts(2), max_new)
        assert [len(got[r]) for r in range(4)] == max_new
        ticks = tr.ticks()
        assert _sum(ticks, "tokens") == sum(max_new)
        assert _sum(ticks, "first") == 4
        assert all(t.get("first", 0) <= t.get("tokens", 0) for t in ticks)

    def test_only_a_tick_that_prefilled_counts_stalled_slots(self):
        """Budgets 9 and 2 on two slots: the second slot frees while the
        first still decodes, so the next prompt's prefill finds ONE slot
        owed a token, and the pass that ran it hands that token out."""
        cb = _predictor(name="st0")
        stall0 = obs.counter("serving.decode_stall_slot_seconds").value(
            replica="st0")
        got = _stream(cb, _prompts(3, (5, 6, 7)), [9, 2, 2])
        assert [len(got[r]) for r in range(3)] == [9, 2, 2]
        ticks = tr.ticks()
        assert all(t.get("pf_stalled", 0) == 0 for t in ticks
                   if not t.get("pf_s"))
        stalled = [t for t in ticks if t.get("pf_stalled")]
        assert stalled and all(t["pf_s"] > 0 for t in stalled)
        assert _sum(ticks, "pf_stalled") == 1       # the third prompt's
        for t in stalled:
            # the tokens it hands out that close a gap are the stalled
            # slot's (one step was in flight), the rest are firsts
            assert t["tokens"] - t["first"] == t["pf_stalled"] == 1
        first_round = next(t for t in ticks if "pf_s" in t)
        assert first_round["pf_stalled"] == 0       # nobody decoding yet
        stall = obs.counter("serving.decode_stall_slot_seconds").value(
            replica="st0") - stall0
        assert stall == pytest.approx(
            sum(t["pf_stalled"] * t["pf_s"] for t in stalled))

    def test_prefix_hit_records_no_prefill_and_a_suffix_its_suffix(self):
        cb = _predictor(name="pf0")
        (base,) = _prompts(4, (16,))

        def counted(kind):
            return obs.counter("serving.prefill_tokens").value(
                kind=kind, replica="pf0")

        f0, p0 = counted("forwarded"), counted("padded")
        cb.generate([base], max_new_tokens=3)
        assert counted("forwarded") == f0 + 16
        tr.clear_ticks()
        cb.generate([base], max_new_tokens=3)           # a whole hit
        assert cb.stats["prefix_hits"] == 1
        ticks = tr.ticks()
        assert not any(PF_FIELDS & set(t) for t in ticks)
        assert _sum(ticks, "tokens") == 3
        assert not any(t["prefill"] for t in ticks)
        tr.clear_ticks()
        cb.generate([base + [7, 8, 9]], max_new_tokens=3)   # a partial hit
        assert cb.stats["prefix_partial_hits"] == 1
        ticks = tr.ticks()
        assert _sum(ticks, "pf_n") == 1
        assert _sum(ticks, "pf_tokens") == 3            # 19 less 16 covered
        assert _sum(ticks, "pf_padded") == 8            # 1 row of bucket 8
        assert counted("forwarded") == f0 + 16 + 3
        assert counted("padded") == p0 + 16 + 8

    def test_the_rounds_span_carries_the_same_numbers(self):
        cb = _predictor()
        cb.generate(_prompts(5, (5, 11)), max_new_tokens=2)
        (sp,) = [s for s in tr.flight_recorder().spans()
                 if s["name"] == "serve.prefill"]
        (t,) = [t for t in tr.ticks() if "pf_s" in t]
        assert sp["labels"]["tokens"] == t["pf_tokens"] == 16
        assert sp["labels"]["padded"] == t["pf_padded"] == 24
        assert sp["labels"]["stalled"] == t["pf_stalled"] == 0
        assert sp["labels"]["seconds"] == pytest.approx(t["pf_s"])

    def test_the_sink_and_the_report_show_the_rounds_numbers(self, tmp_path):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "tools", "trace_report.py"))
        report = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(report)
        path = str(tmp_path / "t.jsonl")
        obs.configure(path)
        try:
            _predictor().generate(_prompts(8, (5, 11)), max_new_tokens=2)
        finally:
            obs.configure(None)
        spans = report.load_spans(path)
        (lab,) = [s["labels"] for s in spans
                  if s["name"] == "serve.prefill"]
        assert (lab["tokens"], lab["padded"], lab["stalled"]) == (16, 24, 0)
        text = report.render(spans)
        assert "tokens forwarded=16  positions computed=24" in text
        assert "padding=33.3%" in text

    def test_a_chunk_of_a_chunked_prefill_counts_tokens_and_no_seconds(self):
        cb = _predictor(prefill_chunk_tokens=8, max_seq_len=96)
        (long_p,) = _prompts(6, (30,))
        out = cb.generate([long_p], max_new_tokens=3)
        assert len(out[0]) == 3 and cb.stats["chunked_requests"] == 1
        ticks = tr.ticks()
        assert _sum(ticks, "pf_tokens") == _sum(ticks, "pf_chunk") == 30
        assert _sum(ticks, "pf_padded") >= 30
        assert _sum(ticks, "pf_s") == 0 and _sum(ticks, "pf_n") == 0
        assert _sum(ticks, "pf_stalled") == 0
        assert _sum(ticks, "tokens") == 3 and _sum(ticks, "first") == 1

    def test_add_sums_where_note_overwrites(self):
        with tr.tick("t") as t:
            t.add(pf_s=0.25, pf_n=1)
            t.add(pf_s=0.5, pf_n=2)
            t.note(tokens=3)
            t.note(tokens=4)
        rec = tr.ticks()[-1]
        assert rec["pf_s"] == 0.75 and rec["pf_n"] == 3
        assert rec["tokens"] == 4
        assert tr.NULL_TICK.add(pf_s=1.0) is None

    def test_disabled_leaves_no_field_no_entry_and_no_operation(self):
        import gc
        import jax
        import jax.numpy as jnp
        model = _model()
        prompts = _prompts(7)
        ref = _predictor(model).generate(prompts, max_new_tokens=4)
        tr.clear_ticks()
        n_gc = len(obsrt.gc_log())
        tokens0 = obs.counter("serving.prefill_tokens").value(
            kind="forwarded")

        def accounted(x):
            with tr.tick("t") as t:
                with t.stage("t.prefill", kind="batch", tokens=3):
                    y = (x * 2.0).sum()
                t.add(pf_tokens=3, pf_s=0.1)
                t.note(tokens=1, first=1)
            return y

        x = jnp.ones((4,))
        j_on = jax.make_jaxpr(accounted)(x)
        obs.enabled(False)
        try:
            got = _predictor(model).generate(prompts, max_new_tokens=4)
            gc.collect()
            j_off = jax.make_jaxpr(accounted)(x)
        finally:
            obs.enabled(True)
        assert got == ref
        assert str(j_on) == str(j_off) == str(
            jax.make_jaxpr(lambda x: (x * 2.0).sum())(x))
        assert [t for t in tr.ticks() if t["name"] == "serve.tick"] == []
        assert len(obsrt.gc_log()) == n_gc
        assert obs.counter("serving.prefill_tokens").value(
            kind="forwarded") == tokens0


# ----------------------------------------------------------------- gc log --
class TestGcLog:
    def test_a_full_collection_is_stamped_between_two_clock_reads(self):
        import gc
        import time
        n0 = obs.counter("host.gc_collections").value(generation="2")
        s0 = obs.counter("host.gc_seconds").value(generation="2")
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
        (e,) = [e for e in obsrt.gc_log(since=t0, until=t1)
                if e["generation"] == 2]
        assert set(e) == {"t", "generation", "seconds", "collected"}
        assert t0 <= e["t"] and e["t"] + e["seconds"] <= t1
        assert e["seconds"] > 0 and e["collected"] >= 0
        assert obs.counter("host.gc_collections").value(
            generation="2") == n0 + 1
        assert obs.counter("host.gc_seconds").value(
            generation="2") == pytest.approx(s0 + e["seconds"])
        assert obsrt.gc_log(since=t1) == [] or \
            obsrt.gc_log(since=t1)[0]["t"] >= t1

    def test_a_short_young_collection_leaves_nothing_a_long_one_an_entry(
            self, monkeypatch):
        n = len(obsrt.gc_log())
        info = {"generation": 0, "collected": 0, "uncollectable": 0}
        obsrt._on_gc("start", info)
        obsrt._on_gc("stop", info)
        assert len(obsrt.gc_log()) == n
        obsrt._on_gc("start", dict(info, generation=1))
        monkeypatch.setattr(obsrt, "_gc_t0", obsrt._gc_t0 - 0.002)
        obsrt._on_gc("stop", dict(info, generation=1, collected=5))
        e = obsrt.gc_log()[-1]
        assert len(obsrt.gc_log()) == n + 1
        assert e["generation"] == 1 and e["collected"] == 5
        assert 0.002 <= e["seconds"] < 0.1

    @pytest.mark.parametrize("held", ["registry", "family", "series"])
    def test_a_collection_met_inside_the_registry_does_not_wait_for_itself(
            self, held, monkeypatch):
        """A collection starts between two bytecodes of whatever the
        thread does, inside the registry's critical sections too, and
        its callback counts into the registry: under plain locks the
        thread waited for itself for ever (tier-1 runs that never
        ended: PRs 40 and 43). On a registry of its own, in a thread of
        its own, so that a fault costs this test and not the process."""
        import gc
        from paddle_tpu.observability.metrics import MetricRegistry
        reg = MetricRegistry()
        monkeypatch.setattr(obsrt, "get_registry", lambda: reg)
        family = reg.counter("host.gc_collections")
        lock = {"registry": reg, "family": family,
                "series": family.labels(generation="2")}[held]._lock

        def collect_inside():
            with lock:
                gc.collect()

        t = threading.Thread(target=collect_inside, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert family.value(generation="2") >= 1

    def test_hook_registered_once(self):
        import gc
        obsrt.watch_gc()
        obsrt.watch_gc()
        assert gc.callbacks.count(obsrt._on_gc) == 1

    def test_log_records_stay_off_the_collectors_books_and_are_bounded(self):
        import gc
        gc.collect()
        assert obsrt._gc_log.maxlen == obsrt._GC_LOG_CAPACITY
        for rec in obsrt._gc_log:
            assert not gc.is_tracked(rec)
            assert all(isinstance(v, (int, float)) for v in rec.values())
        # what the reader hands out is a copy: the log's own stays flat
        obsrt.gc_log()[-1]["note"] = []
        assert "note" not in obsrt._gc_log[-1]

    def test_a_full_collection_is_annotated_on_the_profilers_clock(
            self, tmp_path):
        import gc
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        names = set()
        for pb in glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True):
            for plane in jax.profiler.ProfileData.from_file(pb).planes:
                for line in plane.lines:
                    names |= {ev.name.split("#")[0] for ev in line.events}
        assert "host.gc" in names


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
