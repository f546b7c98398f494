"""Trainer tests: loop, checkpoint auto-resume parity, preemption hook,
speed meter. Oracle (reference style, test/collective/fleet): a run
interrupted at step k and resumed must produce the same final loss as an
uninterrupted run."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.trainer import (SpeedMeter, Trainer, TrainingArguments,
                                device_peak_flops)


def _make(seed=0):
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 32), nn.Tanh(), nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    return model, opt


def _data_iter_fn(start_step):
    def gen():
        step = start_step
        while True:
            rs = np.random.RandomState(step)  # deterministic per step
            x = rs.randn(16, 8).astype(np.float32)
            y = rs.randn(16, 4).astype(np.float32)
            yield paddle.to_tensor(x), paddle.to_tensor(y)
            step += 1
    return gen()


def _loss_fn(out, y):
    return F.mse_loss(out, y)


class TestTrainerLoop:
    def test_basic_run(self, tmp_path):
        model, opt = _make()
        args = TrainingArguments(output_dir=str(tmp_path), max_steps=6,
                                 logging_steps=2, save_steps=100)
        tr = Trainer(model, opt, _loss_fn, args, _data_iter_fn,
                     tokens_per_batch=16)
        res = tr.train()
        assert res["final_step"] == 6
        assert np.isfinite(res["final_loss"])
        assert len(res["logs"]) == 3
        # loss decreases on this stationary-ish problem
        assert res["logs"][-1]["loss"] < res["logs"][0]["loss"] * 1.5

    def test_resume_matches_uninterrupted(self, tmp_path):
        # uninterrupted reference: 8 steps
        model, opt = _make(seed=7)
        args_a = TrainingArguments(output_dir=str(tmp_path / "a"),
                                   max_steps=8, logging_steps=8,
                                   save_steps=100)
        ref = Trainer(model, opt, _loss_fn, args_a, _data_iter_fn).train()

        # interrupted: 4 steps (checkpoint), then fresh process state resumes
        out_b = str(tmp_path / "b")
        model2, opt2 = _make(seed=7)
        args_b1 = TrainingArguments(output_dir=out_b, max_steps=4,
                                    logging_steps=4, save_steps=4)
        Trainer(model2, opt2, _loss_fn, args_b1, _data_iter_fn).train()

        model3, opt3 = _make(seed=7)  # fresh weights — must be overwritten
        args_b2 = TrainingArguments(output_dir=out_b, max_steps=8,
                                    logging_steps=8, save_steps=100)
        tr3 = Trainer(model3, opt3, _loss_fn, args_b2, _data_iter_fn)
        res = tr3.train()
        assert res["start_step"] == 4  # resumed, not restarted
        np.testing.assert_allclose(res["final_loss"], ref["final_loss"],
                                   rtol=1e-4)

    def test_preemption_checkpoints_and_exits(self, tmp_path):
        model, opt = _make()
        args = TrainingArguments(output_dir=str(tmp_path), max_steps=100,
                                 logging_steps=5, save_steps=1000)
        tr = Trainer(model, opt, _loss_fn, args, _data_iter_fn)
        orig = tr._step_obj

        class CountingStep:
            def __init__(self):
                self.n = 0

            @property
            def opt_state(self):
                return orig.opt_state

            _opt_state = property(lambda s: orig._opt_state)

            def __call__(self, *b):
                self.n += 1
                if self.n == 3:
                    tr._preempted = True  # simulate SIGTERM delivery
                return orig(*b)

        tr._step_obj = CountingStep()
        res = tr.train(resume=False)
        assert res["preempted"] and res["final_step"] == 3
        # checkpoint written at the preemption boundary
        model2, opt2 = _make()
        args2 = TrainingArguments(output_dir=str(tmp_path), max_steps=4,
                                  logging_steps=4, save_steps=100)
        tr2 = Trainer(model2, opt2, _loss_fn, args2, _data_iter_fn)
        res2 = tr2.train()
        assert res2["start_step"] == 3


class TestTrainerHybridParallel:
    def test_dp2_mp2_sharding3(self, tmp_path):
        """Trainer drives DistTrainStep over the 8-device CPU mesh with
        dp=2 x mp=2 and ZeRO-3 param sharding; loss finite + decreasing-ish
        and checkpoints written."""
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        paddle.seed(0)
        cfg = LlamaConfig.tiny(tensor_parallel=True)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        args = TrainingArguments(output_dir=str(tmp_path), max_steps=3,
                                 logging_steps=1, save_steps=3,
                                 dp_degree=2, mp_degree=2, sharding_stage=3)

        def data_fn(start):
            def gen():
                s = start
                while True:
                    rs = np.random.RandomState(s)
                    ids = rs.randint(0, cfg.vocab_size, (4, 16))
                    t = paddle.to_tensor(ids.astype(np.int64))
                    yield t, t
                    s += 1
            return gen()

        tr = Trainer(model, opt, lambda lg, lb: crit(lg, lb), args, data_fn,
                     tokens_per_batch=4 * 16)
        res = tr.train()
        assert res["final_step"] == 3
        assert np.isfinite(res["final_loss"])
        ckpts = os.listdir(os.path.join(str(tmp_path), "checkpoints"))
        assert any(c.isdigit() and int(c) == 3 for c in ckpts)

    def test_example_smoke(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        try:
            from examples.llama_pretrain import main
            rc = main(["--smoke", "--output_dir", str(tmp_path),
                       "--max_steps", "3"])
            assert rc == 0
        finally:
            sys.path.pop(0)


class TestSpeedMeter:
    def test_meter(self):
        m = SpeedMeter(n_params=1000, n_devices=1, dtype="float32")
        import time
        m.update(100)
        time.sleep(0.01)
        m.update(100)
        assert m.tokens_per_sec > 0
        # the CPU has no published peak: MFU is not measured here
        assert m.mfu is None

    def test_peak_flops_from_the_table_or_an_error(self):
        from types import SimpleNamespace
        from paddle_tpu.trainer import PEAK_BF16_FLOPS, UnknownDevicePeak
        v5e = SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
        assert device_peak_flops("bfloat16", device=v5e) == 197e12
        assert device_peak_flops("float32", device=v5e) == 197e12 / 2
        assert all(v > 0 for v in PEAK_BF16_FLOPS.values())
        with pytest.raises(UnknownDevicePeak, match="device_kind"):
            device_peak_flops("bfloat16")        # the CPU under test


class TestVisualDLCallback:
    def test_event_file_roundtrip(self, tmp_path):
        """VisualDL callback writes valid TFRecord/tf.Event scalar files
        (framing + masked crc32c verified by re-parsing)."""
        import struct
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi.callbacks import VisualDL
        from paddle_tpu.utils.tbwriter import _masked_crc, LogWriter
        from paddle_tpu.vision.models import LeNet
        from paddle_tpu.vision.datasets import FakeData

        logdir = str(tmp_path / "vdl")
        model = paddle.Model(LeNet())
        model.prepare(paddle.optimizer.Adam(
            learning_rate=1e-3, parameters=model.network.parameters()),
            nn.CrossEntropyLoss(), paddle.metric.Accuracy())
        data = FakeData(size=16, image_shape=(1, 28, 28), num_classes=10)
        model.fit(data, epochs=1, batch_size=8, verbose=0,
                  callbacks=[VisualDL(log_dir=logdir)])

        import os
        files = [f for f in os.listdir(logdir) if "tfevents" in f]
        assert files, os.listdir(logdir)
        raw = open(os.path.join(logdir, files[0]), "rb").read()
        # parse TFRecord stream, verifying CRCs
        off, events = 0, 0
        while off < len(raw):
            (ln,) = struct.unpack("<Q", raw[off:off + 8])
            (crc_len,) = struct.unpack("<I", raw[off + 8:off + 12])
            assert crc_len == _masked_crc(raw[off:off + 8])
            payload = raw[off + 12:off + 12 + ln]
            (crc_data,) = struct.unpack("<I",
                                        raw[off + 12 + ln:off + 16 + ln])
            assert crc_data == _masked_crc(payload)
            events += 1
            off += 16 + ln
        assert events >= 2  # file_version + at least one scalar

        # direct writer API
        w = LogWriter(logdir=str(tmp_path / "w2"))
        w.add_scalar("x/y", 1.5, step=3)
        w.close()


class TestCallbackAndSamplerAdditions:
    def test_subset_random_sampler_and_convert(self):
        import paddle_tpu.io as io
        s = io.SubsetRandomSampler([3, 5, 7])
        assert sorted(s) == [3, 5, 7]
        out = io.default_convert_fn([np.ones(2), {"a": 3}])
        assert out[0].shape == [2]
        assert float(out[1]["a"].numpy()) == 3

    def test_reduce_lr_on_plateau(self):
        cb = paddle.callbacks.ReduceLROnPlateau(monitor="loss",
                                                patience=1, factor=0.5,
                                                verbose=0)

        class FakeOpt:
            def __init__(self):
                self._lr = 0.1

            def get_lr(self):
                return self._lr

            def set_lr(self, v):
                self._lr = v

        class FakeModel:
            pass

        fm = FakeModel()
        fm._optimizer = FakeOpt()
        cb.model = fm
        cb.on_epoch_end(0, {"loss": 1.0})
        cb.on_epoch_end(1, {"loss": 1.0})
        cb.on_epoch_end(2, {"loss": 1.0})
        assert fm._optimizer._lr < 0.1
