"""BERT-Large pretraining-step benchmark — FusedLAMB + fused kernels.

≡ the BASELINE config "BERT-Large pretraining with FusedLAMB +
fused_dense": one full MLM+NSP training step (fwd + bwd + LAMB) on one
chip, sequences/sec printed as JSON.

Run:  python examples/bench_bert.py [--batch 8] [--seq 512] [--iters 10]
"""

from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import json
import time

import jax
import jax.numpy as jnp

from apex_tpu.models.bert import Bert, BertConfig
from apex_tpu.optimizers.fused_lamb import FusedLAMB
from apex_tpu.parallel import mesh as M
from apex_tpu.transformer.training import (
    init_sharded_optimizer,
    make_tp_dp_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--batch-sweep", type=str, default=None,
                    help="comma-separated batches to sweep (round 6: the "
                    "b32 knee question — LAMB's pass is batch-invariant, "
                    "so seq/s keeps rising until compile/HBM fails; "
                    "e.g. '16,32,40,48')")
    args = ap.parse_args()

    if args.batch_sweep:
        # one process per chip: this parent never initialises a JAX
        # backend, so each point's child has the chip to itself (and a
        # failed compile cannot poison the next point's allocator)
        import subprocess
        for b in (int(x) for x in args.batch_sweep.split(",") if x):
            cmd = [_sys.executable, _os.path.abspath(__file__),
                   "--batch", str(b), "--seq", str(args.seq),
                   "--iters", str(args.iters)]
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=1800)
            except subprocess.TimeoutExpired:
                print(f"b{b}: FAIL timeout (1800s)", flush=True)
                continue
            # reverse-scan for the JSON line: a log line after the
            # JSON must not eat the result
            line, platform = "<no json output>", None
            for cand in reversed(r.stdout.strip().splitlines()):
                try:
                    d = json.loads(cand)
                except ValueError:
                    continue
                if isinstance(d, dict) and "metric" in d:
                    line, platform = cand, d.get("platform")
                    break
            if r.returncode != 0:
                line = "FAIL " + r.stderr.strip()[-120:]
            elif platform != "tpu":
                # off-TPU the child clamps itself to b2/s64: every point
                # would be the same measurement wearing different labels
                line = f"FAIL ran on {platform}, not a TPU"
            print(f"b{b}: {line}", flush=True)
        return

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    if not on_tpu:
        args.batch, args.seq, args.iters = 2, 64, 2

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    # flash attention measured fastest at seq 512 too (round-3 sweep:
    # 73.6 vs 66.6 seq/s dense; bf16 MLM logits were neutral-to-worse)
    cfg = (BertConfig(seq_len=args.seq, dtype=jnp.bfloat16,
                      use_flash_attention=True) if on_tpu else
           BertConfig(seq_len=args.seq, hidden=128, num_layers=2,
                      num_heads=4, dtype=jnp.bfloat16))
    model = Bert(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedLAMB(lr=1e-4, weight_decay=0.01)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    del params

    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (args.batch, args.seq), 0,
                                cfg.vocab_size)
    mlm_labels = jnp.roll(tokens, -1, axis=1)
    loss_mask = jax.random.bernoulli(jax.random.PRNGKey(2), 0.15,
                                     (args.batch, args.seq))
    nsp = jax.random.randint(jax.random.PRNGKey(3), (args.batch,), 0, 2)

    def loss_fn(p, tokens, labels):
        return model.loss(p, tokens, labels, loss_mask, nsp_labels=nsp)

    step = make_tp_dp_train_step(model, opt, mesh, loss_fn=loss_fn,
                                 donate=True)

    for _ in range(2):
        opt_state, loss = step(opt_state, tokens, mlm_labels)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        opt_state, loss = step(opt_state, tokens, mlm_labels)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.iters
    print(json.dumps({
        "metric": "bert_large_lamb_seqs_per_sec_per_chip",
        "value": round(args.batch / dt, 1),
        "unit": "sequences/s",
        "s_per_iter": round(dt, 4),
        "vs_baseline": 1.0,
        "platform": jax.devices()[0].platform,
    }))


if __name__ == "__main__":
    main()
