"""Per-component GPT/BERT step anatomy + per-GEMM roofline.

Round 5 (VERDICT r4 next-#3/#8) attributed the missing MFU to
sublayers by timing sub-programs in-jit (slope-timed scans,
dispatch-amortized).  Round 6 (VERDICT r5: "break the plateau or prove
it") descends one level: every individual GEMM of the training step —
QKV, attention-out, MLP-up, MLP-down, LM-head — timed as its three
constituent matmuls (fwd / dgrad / wgrad), each scored against its
SHAPE-ACHIEVABLE peak, not the paper peak:

    achievable(K) = PEAK · min(1, K / 128)

(the v5e MXU is a 128×128 systolic array; a contraction dim K < 128
fills K/128 of it — the d=64 attention matmuls top out at ~98 TF/s no
matter what the kernel does; see /opt guides + docs/PERF.md round-5
attention decomposition).  The flash kernel is scored as its 7-matmul
mix (3 contract over d, 4 over the sequence), and the xent epilogue is
reported as the LM-head row's non-GEMM residue.

Components at the bench configs (350M: b12 s1024; 1.3B: b7 s512;
BERT-Large: b32 s512 bidirectional):
  * embed + LM head + softmax-xent loss (fwd+bwd)
  * one transformer layer's attention sublayer (fwd+bwd) x L
  * one transformer layer's MLP sublayer (fwd+bwd) x L
  * full model step (the reference point)

Usage:
  python scripts/gpt_anatomy.py [350m|1p3b|bert|both]      # sublayer anatomy
  python scripts/gpt_anatomy.py roofline [350m|1p3b|bert|1p3b2k]  # per-GEMM table
  python scripts/gpt_anatomy.py blocks                     # flash block sweep, seq 512
  python scripts/gpt_anatomy.py tune [targets...]          # autotune + re-emit roofline
  python scripts/gpt_anatomy.py tune --check [targets...]  # verify committed defaults
                                                           # (nonzero exit on drift)
  python scripts/gpt_anatomy.py mem [targets...]           # AOT HBM budget tables
                                                           # (compile only, no execute)
  python scripts/gpt_anatomy.py lint [targets...]          # static lint of the bench
                                                           # steps (trace only; nonzero
                                                           # exit on new findings)
  python scripts/gpt_anatomy.py comms [targets...]         # collective inventory +
                                                           # overlap + ICI roofline
                                                           # (compile only, no execute)
  python scripts/gpt_anatomy.py timeline [targets...]      # MEASURED step anatomy from
                                                           # a profiler capture (executes
                                                           # 3 steady steps)
  python scripts/gpt_anatomy.py overlap [targets...]       # predicted-vs-measured
                                                           # per-collective overlap,
                                                           # chunked (overlap_chunks=2)
                                                           # vs monolithic spelling of
                                                           # the same tp=2 SP layer
                                                           # stack (executes both)

`tune` drives apex_tpu.tune.search over each target's flash shape (and
the flat-Adam block at the 1B point), writes the winners to the
persistent cache (apex_tpu.tune.cache_path()), then re-emits the
roofline tables so docs/PERF.md can be refreshed from the same run.
`tune --check` re-sweeps WITHOUT writing and exits 1 if any committed
default (apex_tpu/tune/defaults.py) for this device kind no longer wins
— the CI guard for stale committed configs.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PEAK = 197e12
MXU = 128


def _scan_time(fn, args, iters=50, reps=3):
    def make(length):
        def many(*a):
            def body(carry, _):
                out = fn(*((a[0] + carry.astype(a[0].dtype),) + a[1:]))
                return sum(jnp.sum(l.astype(jnp.float32))
                           for l in jax.tree.leaves(out)) * 1e-30, None
            c, _ = lax.scan(body, jnp.zeros((), jnp.float32), None,
                            length=length)
            return c
        return jax.jit(many)

    def total(f):
        _ = np.asarray(f(*args))
        best = np.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            _ = np.asarray(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    lo, hi = max(1, iters // 5), iters
    return (total(make(hi)) - total(make(lo))) / (hi - lo)


def anatomy(name, hidden, layers, heads, batch, seq, vocab=50304,
            causal=True):
    print(f"--- {name}: h{hidden} L{layers} H{heads} b{batch} s{seq}",
          flush=True)
    key = jax.random.PRNGKey(0)
    d = hidden // heads
    x = jax.random.normal(key, (batch, seq, hidden), jnp.bfloat16)

    # attention sublayer: qkv proj + flash + out proj
    from apex_tpu.ops.flash_attention import flash_attention
    wqkv = jax.random.normal(key, (hidden, 3 * hidden), jnp.bfloat16) * 0.02
    wo = jax.random.normal(key, (hidden, hidden), jnp.bfloat16) * 0.02

    def attn(x, wqkv, wo):
        qkv = x @ wqkv
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads_of(t):
            return t.reshape(batch, seq, heads, d).transpose(0, 2, 1, 3)

        o = flash_attention(heads_of(q), heads_of(k), heads_of(v),
                            causal=causal)
        o = o.transpose(0, 2, 1, 3).reshape(batch, seq, hidden)
        return o @ wo

    def attn_fb(x, wqkv, wo):
        out, vjp = jax.vjp(attn, x, wqkv, wo)
        return (out,) + vjp(out)

    t_attn = _scan_time(attn_fb, (x, wqkv, wo), iters=20)
    fl_attn = (2 * batch * seq * hidden * 4 * hidden       # proj
               + 2 * batch * heads * seq * seq * d * 2) * 3  # sdpa
    print(f"attn sublayer fwd+bwd: {t_attn*1e3:7.3f} ms x{layers} = "
          f"{t_attn*layers*1e3:7.1f} ms  ({fl_attn/t_attn/1e12:.0f} TF/s"
          f" {100*fl_attn/t_attn/PEAK:.0f}%pk)", flush=True)

    # MLP sublayer
    w1 = jax.random.normal(key, (hidden, 4 * hidden), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(key, (4 * hidden, hidden), jnp.bfloat16) * 0.02

    def mlp(x, w1, w2):
        return (jax.nn.gelu(x @ w1)) @ w2

    def mlp_fb(x, w1, w2):
        out, vjp = jax.vjp(mlp, x, w1, w2)
        return (out,) + vjp(out)

    t_mlp = _scan_time(mlp_fb, (x, w1, w2), iters=20)
    fl_mlp = 2 * batch * seq * hidden * 8 * hidden * 3
    print(f"mlp  sublayer fwd+bwd: {t_mlp*1e3:7.3f} ms x{layers} = "
          f"{t_mlp*layers*1e3:7.1f} ms  ({fl_mlp/t_mlp/1e12:.0f} TF/s "
          f"{100*fl_mlp/t_mlp/PEAK:.0f}%pk)", flush=True)

    # LM head + loss (tied embedding matmul + xent)
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    emb = jax.random.normal(key, (vocab, hidden), jnp.bfloat16) * 0.02
    labels = jax.random.randint(key, (batch, seq), 0, vocab)

    def head(x, emb):
        logits = (x @ emb.T).astype(jnp.bfloat16)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, vocab), labels.reshape(-1)))

    def head_fb(x, emb):
        out, vjp = jax.vjp(head, x, emb)
        return (out,) + vjp(jnp.ones_like(out))

    t_head = _scan_time(head_fb, (x, emb), iters=10)
    fl_head = 2 * batch * seq * hidden * vocab * 3
    print(f"LM head + xent fwd+bwd: {t_head*1e3:6.3f} ms          "
          f"({fl_head/t_head/1e12:.0f} TF/s "
          f"{100*fl_head/t_head/PEAK:.0f}%pk)", flush=True)

    # LayerNorm stack (2 per layer + final)
    from apex_tpu.ops.layer_norm import fused_layer_norm
    g = jnp.ones((hidden,))
    bb = jnp.zeros((hidden,))

    def ln_fb(x, g, bb):
        out, vjp = jax.vjp(lambda x, g, bb: fused_layer_norm(x, g, bb),
                           x, g, bb)
        return (out,) + vjp(out)

    t_ln = _scan_time(ln_fb, (x, g, bb), iters=50)
    n_ln = 2 * layers + 1
    print(f"layernorm fwd+bwd:     {t_ln*1e3:7.3f} ms x{n_ln} = "
          f"{t_ln*n_ln*1e3:7.1f} ms", flush=True)

    model_sum = (t_attn + t_mlp) * layers + t_head + t_ln * n_ln
    tot_fl = (fl_attn + fl_mlp) * layers + fl_head
    print(f"component sum: {model_sum*1e3:.1f} ms "
          f"({batch*seq/model_sum:,.0f} tok/s if additive; "
          f"model flops {tot_fl/1e12:.1f} TF)", flush=True)


# ------------------------------ per-GEMM roofline ----------------------------

def _achievable(k_contract):
    """Shape-achievable FLOP/s for one GEMM: the 128-deep contraction
    port of the MXU is the only shape term that matters at these sizes
    (M is always ≥ 3.5k rows and N ≥ 64 lanes pack)."""
    return PEAK * min(1.0, k_contract / MXU)


def _time_gemm(m, k, n, iters=30):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16) * 0.02

    def mm(x, w):
        return jnp.dot(x, w,
                       preferred_element_type=jnp.float32
                       ).astype(jnp.bfloat16)

    return _scan_time(mm, (x, w), iters=iters)


def _gemm_row(label, m, k, n, per_layer=1):
    """One logical GEMM of the step = three matmuls: fwd (M,K)x(K,N),
    dgrad (M,N)x(N,K), wgrad (K,M)x(M,N).  Returns the table row."""
    parts = [("fwd", m, k, n), ("dgrad", m, n, k), ("wgrad", k, m, n)]
    t_tot, floor = 0.0, 0.0
    sub = []
    for pname, pm, pk, pn in parts:
        fl = 2 * pm * pk * pn
        t = _time_gemm(pm, pk, pn)
        t_tot += t
        floor += fl / _achievable(pk)
        sub.append((pname, pk, fl / t / 1e12, _achievable(pk) / 1e12))
    fl_tot = sum(2 * pm * pk * pn for _, pm, pk, pn in parts)
    achieved = fl_tot / t_tot
    achievable = fl_tot / floor
    pct = 100 * achieved / achievable
    print(f"| {label:<22} | {t_tot*1e3*per_layer:7.2f} | "
          f"{achieved/1e12:6.0f} | {achievable/1e12:6.0f} | {pct:5.0f}% |",
          flush=True)
    for pname, pk, a, c in sub:
        print(f"|   · {pname:<18} |         | {a:6.0f} | {c:6.0f} | "
              f"{100*a/c:5.0f}% |  K={pk}", flush=True)
    return t_tot, fl_tot, pct


def _flash_row(batch, heads, seq, d, causal, block_q=None, block_k=None,
               label="flash sdpa (7 mm)"):
    """The attention kernel as a 7-matmul mix: fwd S=QKᵀ + O=PV, bwd
    recompute-S + dP=dO·Vᵀ + dQ + dK + dV.  Three of the seven contract
    over d; the single-block causal config at seq ≤ 1024 executes the
    full square (no skipped blocks), which the executed-flop accounting
    reflects.  With all config args None the kernel consults the
    apex_tpu.tune cache — so a tuned machine's roofline row IS the
    tuned kernel."""
    from apex_tpu.ops.flash_attention import flash_attention
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (batch, heads, seq, d), jnp.bfloat16)
               for kk in keys)
    attn = functools.partial(flash_attention, causal=causal,
                             block_q=block_q, block_k=block_k)

    def fb(q, k, v):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(out)

    t = _scan_time(fb, (q, k, v), iters=15)
    fl_one = 2 * batch * heads * seq * seq * d   # one executed matmul
    fl = 7 * fl_one
    floor = fl_one * (3 / _achievable(d) + 4 / _achievable(seq))
    achieved, achievable = fl / t, fl / floor
    pct = 100 * achieved / achievable
    print(f"| {label:<22} | {t*1e3:7.2f} | {achieved/1e12:6.0f} | "
          f"{achievable/1e12:6.0f} | {pct:5.0f}% |", flush=True)
    return t, fl, pct


def gemm_roofline(name, hidden, layers, heads, batch, seq, vocab=50304,
                  causal=True):
    """Markdown-ready roofline table: per logical GEMM of the training
    step, per-layer fwd+bwd time, achieved vs shape-achievable FLOP/s."""
    d = hidden // heads
    m_rows = batch * seq
    print(f"\n### {name} per-GEMM roofline  (h{hidden} L{layers} "
          f"H{heads} b{batch} s{seq}, M={m_rows})", flush=True)
    print("| GEMM (fwd+dgrad+wgrad) | ms/layer | TF/s | achv | %achv |",
          flush=True)
    print("|---|---|---|---|---|", flush=True)
    _gemm_row("qkv (M,H)x(H,3H)", m_rows, hidden, 3 * hidden)
    from apex_tpu import tune
    cfg = tune.tuned("flash_sdpa",
                     tune.flash_attrs(batch, heads, seq, seq, d,
                                      "bfloat16", causal))
    flabel = ("flash sdpa (7 mm)" if not cfg else
              f"flash tuned q{cfg.get('block_q')}k{cfg.get('block_k')}")
    _flash_row(batch, heads, seq, d, causal, label=flabel)
    _gemm_row("attn_out (M,H)x(H,H)", m_rows, hidden, hidden)
    _gemm_row("mlp_up (M,H)x(H,4H)", m_rows, hidden, 4 * hidden)
    _gemm_row("mlp_down (M,4H)x(4H,H)", m_rows, 4 * hidden, hidden)
    t_lm, _, _ = _gemm_row("lm_head (M,H)x(H,V)", m_rows, hidden, vocab)

    # xent epilogue = LM-head+loss time minus its bare GEMMs — the
    # HBM-bound residue the fused bf16 xent (cross_entropy.py) halves
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, seq, hidden), jnp.bfloat16)
    emb = jax.random.normal(key, (vocab, hidden), jnp.bfloat16) * 0.02
    labels = jax.random.randint(key, (batch, seq), 0, vocab)

    def head(x, emb):
        logits = (x @ emb.T).astype(jnp.bfloat16)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.reshape(-1, vocab), labels.reshape(-1)))

    def head_fb(x, emb):
        out, vjp = jax.vjp(head, x, emb)
        return (out,) + vjp(jnp.ones_like(out))

    t_head = _scan_time(head_fb, (x, emb), iters=10)
    traffic = 2 * m_rows * vocab * 2 + m_rows * vocab * 2  # r/w logits + grad
    eps = max(t_head - t_lm, 1e-9)
    print(f"|   · xent epilogue      | {eps*1e3:7.2f} | "
          f"{traffic/eps/1e9:5.0f} GB/s effective (HBM-bound) |  |  |",
          flush=True)


def flash_block_sweep(batch=32, heads=16, seq=512, d=64, causal=False):
    """Flash block re-sweep at seq 512 (the BERT/1.3B shape; the
    round-4 sweep only covered seq 1024)."""
    print(f"--- flash blocks @ b{batch} H{heads} s{seq} d{d} "
          f"causal={causal}", flush=True)
    for bq, bk in ((None, None), (512, 512), (256, 512), (512, 256),
                   (256, 256)):
        try:
            t, _, _ = _flash_row(batch, heads, seq, d, causal,
                                 block_q=bq, block_k=bk,
                                 label=f"blocks ({bq},{bk})")
        except Exception as e:
            print(f"blocks ({bq},{bk}): FAIL {repr(e)[:80]}", flush=True)


def _parse_key_attrs(key):
    """Invert tune.make_key: 'op|k=v,...' → (op, {k: int|bool|str})."""
    op, rest = key.split("|", 1)
    attrs = {}
    for kv in rest.split(","):
        k, v = kv.split("=", 1)
        if k in ("causal", "seg"):
            attrs[k] = v == "1"
        elif v.lstrip("-").isdigit():
            attrs[k] = int(v)
        else:
            attrs[k] = v
    return op, attrs


def _check_committed(committed):
    """Re-sweep EVERY committed default for this device kind (the keys
    themselves name the shapes) and return the list of drifted
    entries — so the CI guard can never silently skip a stale entry."""
    from apex_tpu.tune import search

    drift = []
    for key, entry in sorted(committed.items()):
        op, a = _parse_key_attrs(key)
        want = entry.get("config")
        try:
            if op == "flash_sdpa":
                if (a.get("bias", "none") != "none" or a["sq"] != a["sk"]
                        or "dv" in a or "hkv" in a):
                    print(f"  --check: cannot sweep {key} (unsupported "
                          "key shape); skipping", flush=True)
                    continue
                print(f"--- check {key}", flush=True)
                best, _ = search.tune_flash(
                    a["b"], a["h"], a["sq"], a["d"], dtype=a["dtype"],
                    causal=a["causal"], seg=a["seg"], write=False,
                    verbose=True)
            elif op == "opt_flat":
                print(f"--- check {key}", flush=True)
                best, _ = search.tune_opt_flat(
                    a["rows"] * 128, kernel=a["kernel"], write=False)
            else:
                print(f"  --check: unknown op in {key}; skipping",
                      flush=True)
                continue
        except Exception as e:
            drift.append((key, want, f"SWEEP FAILED: {repr(e)[:80]}"))
            continue
        if best != want:
            drift.append((key, want, best))
            print(f"  DRIFT: committed {want} != fresh {best}",
                  flush=True)
        else:
            print(f"  ok: {want}", flush=True)
    return drift


def tune_mode(targets, check=False):
    """Autotune (or --check) the flash + flat-Adam configs at the bench
    shapes, then re-emit the roofline tables from the tuned cache.
    --check re-sweeps every committed default for this device kind and
    exits nonzero on any drift."""
    from apex_tpu import tune
    from apex_tpu.tune import defaults as tune_defaults
    from apex_tpu.tune import search

    kind = tune.device_kind()
    if check:
        committed = tune_defaults.DEFAULTS.get(kind, {})
        if not committed:
            print(f"tune --check: no committed defaults for device "
                  f"kind {kind!r} — nothing to verify", flush=True)
            return 0
        drift = _check_committed(committed)
        if drift:
            print(f"tune --check: {len(drift)} committed default(s) "
                  "drifted — update apex_tpu/tune/defaults.py:",
                  flush=True)
            for key, want, got in drift:
                print(f"  {key}: committed {want} -> fresh {got}",
                      flush=True)
            return 1
        print("tune --check: all committed defaults match fresh sweeps",
              flush=True)
        return 0
    for t in targets:
        nm, h, L, H, b, s, v, c = CONFIGS[t]
        d = h // H
        print(f"--- tune flash @ {nm}: b{b} H{H} s{s} d{d} causal={c}",
              flush=True)
        best, results = search.tune_flash(b, H, s, d, causal=c,
                                          write=True, verbose=True)
        print(f"  winner: {best} ({results[0][1]*1e3:.3f} ms)",
              flush=True)
    # flat-Adam block at the 1B bench point rides along
    try:
        best, _ = search.tune_opt_flat(10 ** 9, write=True)
        print(f"--- tune opt_flat @ 1B: winner {best}", flush=True)
    except Exception as e:
        print(f"--- tune opt_flat: FAIL {repr(e)[:80]}", flush=True)
    print(f"\ncache written to {tune.cache_path()} "
          f"(fingerprint {tune.fingerprint()}); tuned rooflines:",
          flush=True)
    for t in targets:
        nm, h, L, H, b, s, v, c = CONFIGS[t]
        gemm_roofline(nm, h, L, H, b, s, vocab=v, causal=c)
    return 0


# --------------------------- AOT memory anatomy ---------------------------

def _build_bench_step(t, on_tpu, mode="mem"):
    """Build one CONFIGS target's EXACT bench train step without
    compiling or executing it.  Returns (label, step, abstract args,
    analytic flops) — shared by `mem` (AOT budget) and `lint` (static
    analysis).  On a CPU backend the big configs would take minutes of
    XLA compile (mem) for no extra truth, so the smoke size
    substitutes while KEEPING the model family / optimizer / loss
    shape, so every target's build path stays exercised."""
    import jax.numpy as jnp

    from apex_tpu import monitor
    from apex_tpu.models.bert import Bert, BertConfig
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.optimizers.fused_lamb import FusedLAMB
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    nm, h, L, H, b, s, v, c = CONFIGS[t]
    is_bert = not c  # the one bidirectional bench config
    if on_tpu:
        batch = b
    else:
        print(f"--- {mode} {nm}: CPU backend, shrinking to the smoke "
              "config (structure only; run on TPU for real shapes)",
              flush=True)
        h, L, H, v = 64, 2, 4, 512
        batch, s = 2, 64
    M.destroy_model_parallel()
    if mode == "comms":
        # the comms gate is about COLLECTIVES: a single-device mesh
        # makes every group degenerate (n=1, excluded from the
        # aggregates), so the overlap gate would be vacuously green.
        # Mesh over ALL devices (dp = world, like comms_probe's
        # gpt_zero2 target); the batch must then shard over dp.
        mesh = M.initialize_model_parallel()
        dp = mesh.devices.size
        batch = -(-batch // dp) * dp
    else:
        # mem/lint read the single-program truth; one device keeps
        # the big-config XLA compile affordable
        mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    loss_fn = None
    if is_bert:
        # mirror bench._bert_seq_per_sec: BERT-Large MLM+NSP step
        # with FusedLAMB — the program must be the EXACT one the
        # bench times, not a causal GPT stand-in
        cfg = BertConfig(vocab_size=v, seq_len=s, hidden=h,
                         num_layers=L, num_heads=H,
                         dtype=jnp.bfloat16 if on_tpu
                         else jnp.float32,
                         use_flash_attention=on_tpu)
        model = Bert(cfg)
        loss_mask = jnp.zeros((batch, s), bool)
        nsp = jnp.zeros((batch,), jnp.int32)

        def loss_fn(p, tk, lb):
            return model.loss(p, tk, lb, loss_mask, nsp_labels=nsp)

        opt = FusedLAMB(lr=1e-4, weight_decay=0.01,
                        use_pallas=on_tpu,
                        master_dtype=jnp.bfloat16 if on_tpu
                        else jnp.float32)
        analytic = monitor.bert_step_flops(cfg, batch, seq=s)
    else:
        cfg = (GPTConfig(vocab_size=v, seq_len=s, hidden=h,
                         num_layers=L, num_heads=H, dropout=0.0,
                         dtype=jnp.bfloat16,
                         logits_dtype=jnp.bfloat16, remat=False,
                         use_flash_attention=True) if on_tpu else
               GPTConfig(vocab_size=v, seq_len=s, hidden=h,
                         num_layers=L, num_heads=H, dropout=0.0))
        model = GPT(cfg)
        opt = FusedAdam(lr=1e-4, use_pallas=on_tpu,
                        master_dtype=jnp.bfloat16 if on_tpu
                        else jnp.float32)
        analytic = monitor.gpt_step_flops(cfg, batch, seq=s)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, loss_fn=loss_fn,
                                 donate=True)
    del params
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, s), jnp.int32)
    label = f"{nm}: h{h} L{L} H{H} b{batch} s{s}"
    return label, step, (opt_state, tokens, labels), analytic


def mem_mode(targets):
    """Per-target HBM budget via the compile observatory (ISSUE 5):
    build the EXACT bench train step for each config, AOT lower+compile
    it WITHOUT executing, and print the budget table (params /
    optimizer state / activations+temps), the donation check, and the
    flops cross-check against monitor.flops' analytic accounting — the
    table an operator reads before picking a batch size."""
    from apex_tpu import monitor
    from apex_tpu.parallel import mesh as M

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    rc = 0
    for t in targets:
        label, step, args, analytic = _build_bench_step(t, on_tpu)
        print(f"\n--- mem {label} (AOT, no execution)", flush=True)
        rep = monitor.analyze_step(step, args, analytic_flops=analytic)
        print(monitor.render_budget_table(rep), flush=True)
        if on_tpu and (rep.donation_ok is False or rep.flops_ok is False):
            # a flagged budget is a failed gate, CI-style — but only
            # for the REAL configs; the CPU smoke substitution's flop
            # mix legitimately diverges (NSP/pooler residue at tiny h)
            rc = 1
        M.destroy_model_parallel()
    live = monitor.device_memory_stats()
    if live is not None:
        print(f"\nlive allocator: "
              f"{live.get('bytes_in_use', 0) / 2**30:.2f} GiB in use, "
              f"{live.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB peak",
              flush=True)
    return rc


def lint_mode(targets):
    """Static lint of each target's EXACT bench train step (ISSUE 6):
    trace — never compile, never execute — and run apex_tpu.lint's
    dtype-policy / collective / donation passes.  Nonzero exit on any
    finding outside the committed allowlist
    (scripts/lint_allowlist.txt); `scripts/lint_step.py` is the richer
    CLI (adds the repo AST pass + --selftest)."""
    import os as _os

    from apex_tpu import lint
    from apex_tpu.parallel import mesh as M

    allowlist_path = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)),
        "lint_allowlist.txt")
    allowlist = (lint.load_allowlist(allowlist_path)
                 if _os.path.exists(allowlist_path) else [])
    from apex_tpu.ops._common import on_chip
    on_tpu = on_chip()
    rc = 0
    for t in targets:
        label, step, args, _ = _build_bench_step(t, on_tpu, mode="lint")
        print(f"\n--- lint {label} (trace only, no compile)",
              flush=True)
        findings = lint.lint_step(step, args, program=t)
        new, allowed = lint.apply_allowlist(findings, allowlist)
        rep = lint.LintReport(target=t, new=new, allowlisted=allowed)
        print(lint.render_findings(rep), flush=True)
        if not rep.ok:
            rc = 1
        M.destroy_model_parallel()
    return rc


def comms_mode(targets):
    """Per-target collective inventory + overlap + ICI roofline
    (ISSUE 7): build the EXACT bench train step, AOT lower+compile it
    WITHOUT executing, and print the comms table (`monitor.comms`) —
    what the step says over the interconnect and whether that talk
    hides behind compute.  Nonzero exit when an expected-overlap
    collective serialized on a backend where overlap is measurable
    (TPU); `scripts/comms_probe.py` is the richer CI gate (adds the
    ZeRO-2 dp target, the allowlist, and --selftest)."""
    from apex_tpu import monitor
    from apex_tpu.parallel import mesh as M

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    rc = 0
    for t in targets:
        label, step, args, _ = _build_bench_step(t, on_tpu, mode="comms")
        print(f"\n--- comms {label} (AOT, no execution)", flush=True)
        rep = monitor.comms_report(step, args)
        print(monitor.render_comms_table(rep, label=label), flush=True)
        if rep.async_supported and not rep.overlap_ok:
            rc = 1
        M.destroy_model_parallel()
    return rc


def timeline_mode(targets, n_steps=3):
    """Measured per-step anatomy of each target's EXACT bench train
    step (ISSUE 15): build via the shared builder (comms-style mesh —
    all devices, so the collective lanes are populated), EXECUTE two
    warmup + `n_steps` captured steps under a `ProfileCapture`, and
    print the timeline table `monitor.timeline` parses out of the
    trace — device-busy fraction, host gap, category attribution, and
    (on TPU) the measured per-collective overlap.  Nonzero exit when
    the trace parsed to zero device events or the step count drifted;
    `scripts/timeline_probe.py` is the richer CI gate (adds the ZeRO-2
    dp target, the comms crosscheck, and --selftest)."""
    import tempfile

    import jax.numpy as jnp

    from apex_tpu import monitor
    from apex_tpu.parallel import mesh as M

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    rc = 0
    for t in targets:
        label, step, (opt_state, tokens, labels), _ = \
            _build_bench_step(t, on_tpu, mode="comms")
        tok = jnp.zeros(tokens.shape, tokens.dtype)
        lab = jnp.zeros(labels.shape, labels.dtype)
        state = opt_state
        # two warmups absorb the compile + the donated-layout second
        # compile (the bench.py rule) so the capture holds STEADY steps
        for _ in range(2):
            state, loss = step(state, tok, lab)
        jax.block_until_ready(state)
        cap = monitor.profile_capture(
            range(n_steps),
            logdir=tempfile.mkdtemp(prefix="anatomy_timeline_"))
        try:
            for i in range(n_steps):
                with cap.step(i):
                    state, loss = step(state, tok, lab)
                    jax.block_until_ready(loss)
        finally:
            cap.close()  # a raise mid-capture must stop the profiler
        rep = monitor.analyze_trace(cap.trace_path())
        print(f"\n--- timeline {label} ({n_steps} measured steps)",
              flush=True)
        print(monitor.render_timeline_table(rep, label=label),
              flush=True)
        if rep.n_device_events == 0 or len(rep.steps) != n_steps:
            rc = 1
        M.destroy_model_parallel()
    return rc


def _build_overlap_step(t, on_tpu, chunks):
    """The CONFIGS target rebuilt as a tp=2 SEQUENCE-PARALLEL GPT with
    `overlap_chunks` forced — the chunked (AFTER) vs monolithic
    (BEFORE) spelling of the SAME layer stack for overlap_mode.
    Forcing the chunk count bypasses the tuner so both spellings are
    deterministic on untuned machines; everything else (model dims,
    optimizer, loss, mesh) is held fixed, so any inventory or overlap
    difference between the two is the chunking and nothing else."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    nm, h, L, H, b, s, v, causal = CONFIGS[t]
    if not causal:
        sys.exit(f"overlap mode needs a causal GPT target, not {nm}")
    if on_tpu:
        batch = b
        cfg = GPTConfig(vocab_size=v, seq_len=s, hidden=h,
                        num_layers=L, num_heads=H, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        remat=False, use_flash_attention=True,
                        sequence_parallel=True, overlap_chunks=chunks)
    else:
        print(f"--- overlap {nm}: CPU backend, shrinking to the smoke "
              "config (structure only; run on TPU for measured "
              "overlap)", flush=True)
        h, L, H, v = 64, 2, 4, 512
        batch, s = 2, 64
        cfg = GPTConfig(vocab_size=v, seq_len=s, hidden=h,
                        num_layers=L, num_heads=H, dropout=0.0,
                        sequence_parallel=True, overlap_chunks=chunks)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=2)
    dp = mesh.devices.size // 2
    batch = -(-batch // max(1, dp)) * max(1, dp)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4, use_pallas=on_tpu,
                    master_dtype=jnp.bfloat16 if on_tpu
                    else jnp.float32)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    del params
    tokens = jax.ShapeDtypeStruct((batch, s), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, s), jnp.int32)
    label = f"{nm}: h{h} L{L} H{H} b{batch} s{s} tp2-sp"
    return label, step, (opt_state, tokens, labels)


def _overlap_kind_summary(crep_dict, xc):
    """Per-kind rollup of one spelling: count, MiB, mean predicted and
    mean measured overlap over the counted collectives."""
    rows = {}
    meas_by_name = {r["name"]: r["measured_overlap_fraction"]
                    for r in xc["rows"]}
    for c in crep_dict["collectives"]:
        if c.get("group_size", 1) <= 1:
            continue
        r = rows.setdefault(c["kind"], dict(n=0, bytes=0, pred=[],
                                            meas=[]))
        r["n"] += 1
        r["bytes"] += c["operand_bytes"]
        if c.get("overlap_fraction") is not None:
            r["pred"].append(c["overlap_fraction"])
        m = meas_by_name.get(c["name"])
        if m is not None:
            r["meas"].append(m)
    return rows


def overlap_mode(targets, n_steps=3):
    """BEFORE/AFTER overlap anatomy (ISSUE 18): for each target, build
    the tp=2 sequence-parallel step in its MONOLITHIC (chunks=1) and
    CHUNKED (overlap_chunks=2) spelling, AOT-audit both with the comms
    observatory (predicted overlap), EXECUTE both under a profiler
    capture (measured overlap — TPU only; a CPU capture reports the
    measured plane UNMEASURABLE, honestly), and print the
    predicted-vs-measured crosscheck table per spelling plus a
    per-kind BEFORE/AFTER rollup.  This is the artifact docs/PERF.md's
    "Measured overlap — next TPU session" note asks for: the same
    layer, two spellings, one table.  Nonzero exit when a trace
    parsed broken or (on a measurable backend) a crosscheck row
    DIVERGES."""
    import tempfile

    import jax.numpy as jnp

    from apex_tpu import monitor
    from apex_tpu.monitor import comms as comms_lib
    from apex_tpu.monitor import timeline
    from apex_tpu.parallel import mesh as M

    from apex_tpu.ops._common import on_chip

    on_tpu = on_chip()
    rc = 0
    for t in targets:
        summaries = {}
        for spelling, chunks in (("monolithic", 1), ("chunked", 2)):
            label, step, (opt_state, tokens, labels) = \
                _build_overlap_step(t, on_tpu, chunks)
            crep = comms_lib.comms_report(
                step, (opt_state, tokens, labels))
            tok = jnp.zeros(tokens.shape, tokens.dtype)
            lab = jnp.zeros(labels.shape, labels.dtype)
            state = opt_state
            for _ in range(2):  # compile + donated-layout recompile
                state, loss = step(state, tok, lab)
            jax.block_until_ready(state)
            cap = monitor.profile_capture(
                range(n_steps),
                logdir=tempfile.mkdtemp(prefix="anatomy_overlap_"))
            try:
                for i in range(n_steps):
                    with cap.step(i):
                        state, loss = step(state, tok, lab)
                        jax.block_until_ready(loss)
            finally:
                cap.close()
            rep = monitor.analyze_trace(cap.trace_path())
            xc = timeline.crosscheck_comms(rep, crep)
            print(f"\n--- overlap {label} [{spelling}, "
                  f"chunks={chunks}] ({n_steps} measured steps)",
                  flush=True)
            print(timeline.render_crosscheck(
                xc, label=f"{label} {spelling}"), flush=True)
            if not rep.overlap_measurable:
                print("measured plane: UNMEASURABLE on this backend "
                      "(honest) — predicted inventory still pins the "
                      "chunked pattern", flush=True)
            summaries[spelling] = _overlap_kind_summary(
                crep.to_dict(), xc)
            if rep.n_device_events == 0 or len(rep.steps) != n_steps:
                rc = 1
            if rep.overlap_measurable and not xc["ok"]:
                rc = 1
            M.destroy_model_parallel()

        def _fmt(vals):
            return (f"{100 * sum(vals) / len(vals):5.1f}%" if vals
                    else "  n/a ")

        print(f"\n=== overlap BEFORE/AFTER: {t} ===")
        print("| kind               | spelling   |  n |      MiB | "
              "pred ovl | meas ovl |")
        print("|---|---|---|---|---|---|")
        kinds = sorted(set(summaries["monolithic"])
                       | set(summaries["chunked"]))
        for k in kinds:
            for spelling in ("monolithic", "chunked"):
                r = summaries[spelling].get(k)
                if r is None:
                    print(f"| {k:<18} | {spelling:<10} |  0 |"
                          f"        - |      -   |      -   |")
                    continue
                print(f"| {k:<18} | {spelling:<10} | {r['n']:2d} | "
                      f"{r['bytes'] / 2**20:8.2f} | {_fmt(r['pred'])} "
                      f"| {_fmt(r['meas'])} |")
    return rc


CONFIGS = {
    # name: (hidden, layers, heads, batch, seq, vocab, causal)
    "350m": ("GPT-350M", 1024, 24, 16, 12, 1024, 50304, True),
    "1p3b": ("GPT-1.3B", 2048, 24, 32, 7, 512, 50304, True),
    # the seq-2048 1.3B attention shape — the d=64 plateau point ISSUE 3
    # targets (batch 4 keeps activations on one chip)
    "1p3b2k": ("GPT-1.3B-s2048", 2048, 24, 32, 4, 2048, 50304, True),
    "bert": ("BERT-Large", 1024, 24, 16, 32, 512, 30528, False),
}


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which == "roofline":
        targets = sys.argv[2:] or [t for t in CONFIGS if t != "1p3b2k"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown roofline target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        for t in targets:
            nm, h, L, H, b, s, v, c = CONFIGS[t]
            gemm_roofline(nm, h, L, H, b, s, vocab=v, causal=c)
    elif which == "tune":
        rest = sys.argv[2:]
        check = "--check" in rest
        targets = [t for t in rest if t != "--check"] or list(CONFIGS)
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown tune target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(tune_mode(targets, check=check))
    elif which == "mem":
        targets = sys.argv[2:] or ["350m"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown mem target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(mem_mode(targets))
    elif which == "lint":
        targets = sys.argv[2:] or ["350m", "bert"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown lint target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(lint_mode(targets))
    elif which == "comms":
        targets = sys.argv[2:] or ["350m"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown comms target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(comms_mode(targets))
    elif which == "timeline":
        targets = sys.argv[2:] or ["350m"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown timeline target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(timeline_mode(targets))
    elif which == "overlap":
        targets = sys.argv[2:] or ["350m"]
        bad = [t for t in targets if t not in CONFIGS]
        if bad:
            sys.exit(f"unknown overlap target(s) {bad}; "
                     f"choices: {sorted(CONFIGS)}")
        sys.exit(overlap_mode(targets))
    elif which == "blocks":
        flash_block_sweep(causal=False)   # BERT shape
        flash_block_sweep(batch=7, heads=32, seq=512, causal=True)  # 1.3B
        flash_block_sweep(batch=4, heads=32, seq=2048, causal=True)  # 2k
    elif which == "both":
        for t in ("350m", "1p3b"):
            nm, h, L, H, b, s, v, c = CONFIGS[t]
            anatomy(nm, h, L, H, b, s, vocab=v, causal=c)
    elif which in CONFIGS:
        nm, h, L, H, b, s, v, c = CONFIGS[which]
        anatomy(nm, h, L, H, b, s, vocab=v, causal=c)
    else:
        sys.exit(f"unknown mode {which!r}; expected one of "
                 f"{sorted(CONFIGS)} | both | roofline [target...] | "
                 "blocks | tune [--check] [target...] | mem [target...]"
                 " | lint [target...] | comms [target...] | "
                 "timeline [target...]")
