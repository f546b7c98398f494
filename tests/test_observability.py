"""Observability subsystem (PR 1 tentpole): registry semantics, the
zero-overhead disabled mode, exporter round-trips, serving counters
under a ContinuousBatchingPredictor run, and the dist_step telemetry
acceptance loop on the 8-virtual-device CPU mesh."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


@pytest.fixture(autouse=True)
def _clean_sink():
    """Every test starts with no process sink and ends detached."""
    obs.configure(None)
    yield
    obs.configure(None)
    obs.enabled(True)


# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_labels_are_distinct_series(self):
        r = obs.MetricRegistry()
        c = r.counter("t.calls")
        c.inc(op="all_reduce", axis="data")
        c.inc(2.0, op="all_reduce", axis="data")
        c.inc(op="all_gather", axis="model")
        assert c.value(op="all_reduce", axis="data") == 3.0
        assert c.value(op="all_gather", axis="model") == 1.0
        samples = {tuple(sorted(s.labels.items())): s.value
                   for s in c.samples()}
        assert len(samples) == 2

    def test_gauge_set_inc(self):
        r = obs.MetricRegistry()
        g = r.gauge("t.depth")
        g.set(4)
        g.labels().inc(2)
        assert g.value() == 6.0

    def test_histogram_quantiles_and_stats(self):
        r = obs.MetricRegistry()
        h = r.histogram("t.lat", unit="s")
        for v in range(1, 101):
            h.observe(v / 100.0)
        s = h.labels()
        assert s.count == 100
        assert abs(s.mean - 0.505) < 1e-9
        assert abs(h.quantile(0.5) - 0.505) < 0.02
        assert h.quantile(0.99) > 0.97
        assert h.quantile(0.0) == pytest.approx(0.01)
        assert h.quantile(1.0) == pytest.approx(1.0)
        (sample,) = list(h.samples())
        assert sample.extra["count"] == 100
        assert sample.extra["min"] == pytest.approx(0.01)
        assert sample.extra["max"] == pytest.approx(1.0)

    def test_same_name_returns_same_metric_and_kind_conflict_raises(self):
        r = obs.MetricRegistry()
        assert r.counter("t.x") is r.counter("t.x")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("t.x")

    def test_reset_drops_series_but_keeps_references_working(self):
        r = obs.MetricRegistry()
        c = r.counter("t.y")
        c.inc(5)
        r.reset()
        assert r.collect() == []
        c.inc()  # held reference repopulates
        assert c.value() == 1.0


# ---------------------------------------------------------------------------
class TestDisabledMode:
    def test_disabled_records_zero_entries(self):
        r = obs.MetricRegistry()
        c, g, h = r.counter("d.c"), r.gauge("d.g"), r.histogram("d.h")
        with obs.scoped(False):
            c.inc()
            g.set(3)
            h.observe(0.1)
        assert r.collect() == []  # not even zero-valued series appear

    def test_disabled_emits_nothing_into_jitted_programs(self):
        """The acceptance bar: enabled(False) must cost ZERO at trace
        time — the jaxpr of an instrumented function is identical to the
        uninstrumented one (no debug_callback, same equation count)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.observability.train_metrics import StepTelemetry

        tel = StepTelemetry(n_params=10, n_devices=1)

        def plain(x):
            return (x * 2.0).sum()

        def make_instrumented():
            # fresh function object per trace: jax caches jaxprs by
            # function identity, and the enabled() switch is (by
            # contract) read at trace time
            def instrumented(x):
                y = x * 2.0
                tel.grad_norm_callback([y])
                return y.sum()
            return instrumented

        x = jnp.ones((4,))
        with obs.scoped(False):
            j_plain = jax.make_jaxpr(plain)(x)
            j_off = jax.make_jaxpr(make_instrumented())(x)
        with obs.scoped(True):
            j_on = jax.make_jaxpr(make_instrumented())(x)
        assert "debug_callback" not in str(j_off)
        assert len(j_off.eqns) == len(j_plain.eqns)
        assert "debug_callback" in str(j_on)

    def test_jit_callback_direct(self):
        import jax
        import jax.numpy as jnp
        seen = []

        @jax.jit
        def f(x):
            obs.jit_callback(lambda v: seen.append(float(v)), x.sum())
            return x + 1
        f(jnp.ones((3,)))
        jax.effects_barrier()
        assert seen == [3.0]


# ---------------------------------------------------------------------------
class TestExporters:
    def _registry(self):
        r = obs.MetricRegistry()
        r.counter("e.calls").inc(3, op="all_reduce", axis="data")
        r.gauge("e.depth").set(7)
        h = r.histogram("e.lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(2.0)
        return r

    def test_jsonl_round_trip(self, tmp_path):
        r = self._registry()
        p = str(tmp_path / "t.jsonl")
        with obs.JsonlExporter(p, registry=r) as e:
            e.export(step=1)
            e.export(step=2)
        recs = [json.loads(line) for line in open(p)]
        assert all(set(rec) >= {"ts", "step", "name", "kind", "labels",
                                "value"} for rec in recs)
        by_step = {}
        for rec in recs:
            by_step.setdefault(rec["step"], []).append(rec)
        assert set(by_step) == {1, 2}
        names = {rec["name"] for rec in by_step[1]}
        assert names == {"e.calls", "e.depth", "e.lat"}
        counts = {rec["name"]: rec for rec in by_step[2]}
        assert counts["e.calls"]["value"] == 3.0
        assert counts["e.calls"]["labels"] == {"op": "all_reduce",
                                               "axis": "data"}
        assert counts["e.lat"]["count"] == 3
        assert counts["e.lat"]["p50"] > 0

    def test_prometheus_text_format(self, tmp_path):
        r = self._registry()
        text = obs.PrometheusExporter(registry=r).render()
        assert "# TYPE e_calls counter" in text
        assert 'e_calls{axis="data",op="all_reduce"} 3.0' in text
        assert "# TYPE e_depth gauge" in text
        assert "e_depth 7.0" in text
        # histogram: cumulative buckets, +Inf == count, sum present
        assert 'e_lat_bucket{le="0.1"} 1' in text
        assert 'e_lat_bucket{le="1.0"} 2' in text
        assert 'e_lat_bucket{le="+Inf"} 3' in text
        assert "e_lat_count 3" in text
        path = obs.PrometheusExporter(registry=r).write(
            str(tmp_path / "m.prom"))
        assert open(path).read() == text

    def test_tensorboard_exporter_writes_event_file(self, tmp_path):
        r = self._registry()
        d = str(tmp_path / "tb")
        with obs.TensorBoardExporter(d, registry=r) as e:
            e.export(step=1)
        files = os.listdir(d)
        assert any(f.startswith("events.out.tfevents") for f in files)
        path = os.path.join(d, files[0])
        assert os.path.getsize(path) > 100  # header + scalar records

    def test_env_and_configure_sink(self, tmp_path):
        p = str(tmp_path / "auto.jsonl")
        obs.configure(jsonl_path=p)
        assert obs.telemetry_path() == p
        obs.counter("e.auto").inc()
        obs.maybe_export(step=9)
        obs.configure(None)
        recs = [json.loads(line) for line in open(p)]
        assert any(rec["name"] == "e.auto" and rec["step"] == 9
                   for rec in recs)


# ---------------------------------------------------------------------------
class TestServingMetrics:
    def test_counters_increment_under_continuous_batching(self):
        from paddle_tpu.inference import ContinuousBatchingPredictor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        reg = obs.get_registry()

        def val(name, **labels):
            m = reg.get(name)
            return m.value(**labels) if m is not None else 0.0

        adm0 = val("serving.admissions")
        evt0 = val("serving.evictions")
        rej0 = val("serving.rejected_requests", reason="over_max_seq_len")
        ttft0 = (reg.get("serving.ttft_seconds").labels().count
                 if reg.get("serving.ttft_seconds") else 0)
        rng = np.random.RandomState(0)
        vocab = model.config.vocab_size
        prompts = [rng.randint(2, vocab, (n,)).tolist()
                   for n in (5, 11, 3, 8)]
        cb = ContinuousBatchingPredictor(model, max_batch_size=2,
                                         page_size=8, max_seq_len=64)
        overlong = [2] * 61          # 61 + 4 new > max_seq_len 64
        out = cb.generate(prompts + [overlong], max_new_tokens=4,
                          strict=False)
        assert all(len(o) == 4 for o in out[:4]) and out[4] == []
        assert val("serving.admissions") - adm0 == 4
        assert val("serving.evictions") - evt0 == 4
        assert val("serving.rejected_requests",
                   reason="over_max_seq_len") - rej0 == 1
        assert val("serving.completed_requests", status="ok") >= 4
        h = reg.get("serving.ttft_seconds").labels()
        assert h.count - ttft0 == 4
        assert reg.get("serving.token_latency_seconds").labels().count > 0
        assert reg.get("serving.page_utilization") is not None
        assert cb.last_status == ["ok"] * 4 + ["rejected_over_max_seq_len"]


# ---------------------------------------------------------------------------
class TestDistStepTelemetry:
    def test_20_step_dist_run_writes_full_series(self, tmp_path):
        """The PR acceptance loop: 20 fleet.DistTrainStep steps on the
        8-virtual-device CPU mesh must produce a JSONL telemetry file
        with step_time, tokens/s, MFU, grad-norm, per-axis collective
        bytes and memory watermark series."""
        import jax
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet

        path = str(tmp_path / "telemetry.jsonl")
        obs.configure(jsonl_path=path)
        # registry series are process-global and cumulative: earlier
        # tests (test_distributed) may already have trained through
        # instrumented steps, so assert deltas
        reg = obs.get_registry()
        steps0 = reg.counter("train.steps").value()
        h0 = reg.histogram("train.step_time_seconds").labels().count
        mesh = dist.build_mesh(dp=8)
        dist.set_mesh(mesh)
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.Adam(0.05, parameters=m.parameters())
        step = fleet.DistTrainStep(m, opt,
                                   lambda o, y: F.mse_loss(o, y),
                                   mesh=mesh)
        rng = np.random.RandomState(0)
        x = rng.rand(8, 8).astype(np.float32)
        y = rng.rand(8, 4).astype(np.float32)
        for _ in range(20):
            loss = step(paddle.to_tensor(x), paddle.to_tensor(y))
        assert np.isfinite(float(loss))
        jax.effects_barrier()       # drain the async grad-norm callbacks
        obs.maybe_export(step=21)   # final snapshot includes their writes
        obs.configure(None)

        recs = [json.loads(line) for line in open(path)]
        series = {}
        for rec in recs:
            series.setdefault(rec["name"], []).append(rec)
        for required in ("train.step_time_seconds", "train.tokens_per_sec",
                         "train.grad_norm", "comm.bytes",
                         "mem.bytes_in_use", "mem.peak_bytes_in_use",
                         "train.steps", "train.tokens"):
            assert required in series, (required, sorted(series))
        # 20 per-step snapshots + the final flush
        assert len(series["train.steps"]) == 21
        assert series["train.steps"][-2]["value"] == steps0 + 20
        assert series["train.step_time_seconds"][-1]["count"] == h0 + 20
        assert series["train.tokens_per_sec"][-1]["value"] > 0
        # a utilisation needs a chip with a published peak: on the CPU
        # the series is absent, not estimated
        assert "train.mfu" not in series
        assert series["train.grad_norm"][-1]["value"] > 0
        comm = [rec for rec in series["comm.bytes"]
                if rec["labels"].get("axis") == "data"
                and rec["labels"].get("op") == "all_reduce"]
        assert comm and comm[-1]["value"] > 0
        assert series["mem.bytes_in_use"][-1]["value"] > 0

    def test_disabled_step_has_no_telemetry_and_no_callback(self, tmp_path):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet

        path = str(tmp_path / "none.jsonl")
        obs.configure(jsonl_path=path)
        mesh = dist.build_mesh(dp=8)
        dist.set_mesh(mesh)
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.Adam(0.05, parameters=m.parameters())
        rng = np.random.RandomState(0)
        x = rng.rand(8, 8).astype(np.float32)
        y = rng.rand(8, 4).astype(np.float32)
        with obs.scoped(False):
            step = fleet.DistTrainStep(m, opt,
                                       lambda o, y_: F.mse_loss(o, y_),
                                       mesh=mesh)
            for _ in range(2):
                step(paddle.to_tensor(x), paddle.to_tensor(y))
        obs.configure(None)
        # no instrumentation object, no sink writes
        assert step._obs is None
        assert not os.path.exists(path) or not open(path).read().strip()


# ---------------------------------------------------------------------------
class TestRankHeartbeat:
    def test_interval_nonpositive_disables(self, tmp_path):
        p = str(tmp_path / "hb.jsonl")
        hb = obs.RankHeartbeat(p, interval=0)
        assert hb.due() is False
        assert hb.beat(rank=0) is False
        hb.close()
        assert not os.path.exists(p)  # disabled: file never created
        hb2 = obs.RankHeartbeat(p, interval=-1.0)
        assert hb2.beat() is False and not os.path.exists(p)

    def test_due_gates_and_beat_throttles(self, tmp_path):
        p = str(tmp_path / "hb.jsonl")
        hb = obs.RankHeartbeat(p, interval=60.0)
        assert hb.due() is True           # first beat always due
        assert hb.beat(rank=3, phase="x") is True
        assert hb.due() is False          # within the interval
        assert hb.beat(rank=3) is False   # throttled, nothing written
        hb.close()
        recs = [json.loads(line) for line in open(p)]
        assert len(recs) == 1
        assert recs[0]["kind"] == "heartbeat"
        assert recs[0]["rank"] == 3 and recs[0]["phase"] == "x"

    def test_zero_interval_via_close_and_write_failure(self, tmp_path):
        p = str(tmp_path / "hb.jsonl")
        hb = obs.RankHeartbeat(p, interval=0.0)
        assert hb._f is None              # no fd held while disabled
        hb.close()                        # close on disabled: no-op
        hb2 = obs.RankHeartbeat(str(tmp_path / "hb2.jsonl"),
                                interval=1e-9)
        hb2._f.close()                    # simulate a torn-down fd
        assert hb2.beat(rank=0) is False  # write failure -> False
        hb2._f = None                     # avoid double close
        hb2.close()


class TestSinkLifecycle:
    def test_configure_swap_under_active_sink(self, tmp_path):
        p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        obs.configure(p1)
        first = obs.get_registry().counter("sw.x")
        first.inc()
        obs.maybe_export(step=1)
        obs.configure(p2)                 # swap closes the old exporter
        assert obs.telemetry_path() == p2
        first.inc()
        obs.maybe_export(step=2)
        obs.configure(None)               # detach
        assert obs.telemetry_path() is None
        obs.maybe_export(step=3)          # no sink: silent no-op
        steps1 = {json.loads(l)["step"] for l in open(p1)}
        steps2 = {json.loads(l)["step"] for l in open(p2)}
        assert 1 in steps1 and 2 not in steps1
        assert 2 in steps2 and 1 not in steps2

    def test_jsonl_close_idempotent_and_late_writes_noop(self, tmp_path):
        p = str(tmp_path / "c.jsonl")
        e = obs.JsonlExporter(p)
        e.write_record({"kind": "x", "v": 1})
        e.close()
        e.close()                         # second close: no-op
        e.write_record({"kind": "x", "v": 2})  # after close: dropped
        e.export(step=9)
        e.flush()
        recs = [json.loads(l) for l in open(p)]
        assert [r["v"] for r in recs] == [1]

    def test_atexit_hook_flushes_pending_sink(self, tmp_path):
        """The registered atexit hook closes a still-attached sink, so
        the final partial snapshot reaches disk on teardown."""
        from paddle_tpu.observability import runtime as rt
        p = str(tmp_path / "exit.jsonl")
        obs.configure(p)
        obs.get_registry().counter("exit.x").inc()
        obs.maybe_export(step=1)
        rt._close_sink_at_exit()          # what atexit will run
        assert rt.telemetry_path() is None
        assert any(json.loads(l)["name"] == "exit.x" for l in open(p))
        rt._close_sink_at_exit()          # idempotent on empty state
