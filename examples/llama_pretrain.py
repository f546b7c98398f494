"""North-star recipe: Llama causal-LM pretraining with Fleet hybrid
parallelism (SURVEY.md §7 M7; BASELINE.md north star — sharding-3 + TP).

Single host (one TPU chip or CPU smoke):
    python examples/llama_pretrain.py --smoke

Multi-process / multi-host via the launcher:
    python -m paddle_tpu.distributed.launch --nproc_per_node N \
        examples/llama_pretrain.py -- --dp 2 --mp 2 --sharding 3

Elastic restart: the Trainer auto-resumes from output_dir/checkpoints; on
SIGTERM (TPU preemption / launcher restart) it checkpoints and exits so
the relaunch continues from the same step.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import argparse

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny config for CPU/CI")
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--mp", type=int, default=1)
    p.add_argument("--sharding", type=int, default=0, choices=[0, 1, 2, 3])
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--max_steps", type=int, default=100)
    p.add_argument("--save_steps", type=int, default=50)
    p.add_argument("--output_dir", type=str, default="output/llama")
    p.add_argument("--lr", type=float, default=3e-4)
    args = p.parse_args(argv)

    if args.smoke:
        # dev-box mode: the CPU backend (with virtual devices for
        # --dp/--mp), set BEFORE jax is imported — no accelerator is touched
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.trainer import Trainer, TrainingArguments

    paddle.seed(42)
    tp = args.mp > 1
    if args.smoke:
        cfg = LlamaConfig.tiny(tensor_parallel=tp)
        args.batch, args.seq = max(args.dp * 2, 2), 64
        args.max_steps = min(args.max_steps, 5)
    else:
        # 7B-shaped unless on a single small chip; scaled-down default here
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=16,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=args.seq,
                          tensor_parallel=tp)

    model = LlamaForCausalLM(cfg)
    if jax.default_backend() == "tpu":
        model.bfloat16()
    crit = LlamaPretrainingCriterion(cfg)
    sched = paddle.optimizer.lr.CosineAnnealingDecay(
        learning_rate=args.lr, T_max=args.max_steps)
    opt = paddle.optimizer.AdamW(learning_rate=sched,
                                 parameters=model.parameters(),
                                 weight_decay=0.1)

    def data_iter_fn(start_step):
        def gen():
            step = start_step
            while True:
                rs = np.random.RandomState(step)  # synthetic corpus
                ids = rs.randint(0, cfg.vocab_size,
                                 (args.batch, args.seq)).astype(np.int64)
                t = paddle.to_tensor(ids)
                yield t, t  # labels == inputs (shifted inside criterion)
                step += 1
        return gen()

    targs = TrainingArguments(
        output_dir=args.output_dir, max_steps=args.max_steps,
        logging_steps=10 if not args.smoke else 1,
        save_steps=args.save_steps, bf16=jax.default_backend() == "tpu",
        dp_degree=args.dp, mp_degree=args.mp, sharding_stage=args.sharding)
    trainer = Trainer(model, opt, lambda lg, lb: crit(lg, lb), targs,
                      data_iter_fn,
                      tokens_per_batch=args.batch * args.seq)
    res = trainer.train()
    print({k: res[k] for k in ("start_step", "final_step", "final_loss",
                               "tokens_per_sec", "mfu")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
