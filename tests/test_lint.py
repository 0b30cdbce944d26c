"""apex_tpu.lint — the static program/source linter (ISSUE 6).

Seeded-violation fixtures for every rule (a deliberate fp32 GEMM, an
fp16 psum, a missing donation, an `.item()` in a jitted fn, ...)
asserting rule id + location; clean-program zero-findings tests on the
REAL `ddp.make_train_step` / `make_tp_dp_train_step` programs; the
allowlist/suppression machinery; the `lint_step.py --selftest`
schema-drift gate; and the repo-wide AST pass over apex_tpu/ itself.

Everything here traces — nothing compiles or executes a step — so the
whole file stays cheap inside the tier-1 window.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np  # noqa: F401
import pytest

from apex_tpu import lint
from apex_tpu.lint import LintConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
SDS = jax.ShapeDtypeStruct


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ------------------------- dtype-policy pass -------------------------

def test_dp101_fp32_gemm_in_bf16_region():
    def f(x32, xbf, w):
        a = xbf @ w                       # the policy-conformant GEMM
        b = x32 @ x32.T                   # the fp32 offender
        return a.astype(jnp.float32).sum() + b.sum()

    fs = lint.lint_program(
        f, (SDS((64, 64), jnp.float32), SDS((64, 64), jnp.bfloat16),
            SDS((64, 64), jnp.bfloat16)), program="seed")
    hits = [f for f in fs if f.rule == "DP101"]
    assert len(hits) == 1
    assert "dot_general" in hits[0].location
    assert hits[0].location.startswith("seed:")

    # explicit declared dtype works too (no inference)
    fs2 = lint.lint_program(
        f, (SDS((64, 64), jnp.float32), SDS((64, 64), jnp.bfloat16),
            SDS((64, 64), jnp.bfloat16)),
        config=LintConfig(compute_dtype="bfloat16"))
    assert [f.rule for f in fs2 if f.rule == "DP101"] == ["DP101"]


def test_dp101_not_in_fp32_region():
    def f(x, w):
        return (x @ w).sum()

    fs = lint.lint_program(f, (SDS((64, 64), jnp.float32),
                               SDS((64, 64), jnp.float32)))
    assert rules_of(fs) == []


def test_dp102_lossy_roundtrip():
    def f(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    fs = lint.lint_program(f, (SDS((128, 128), jnp.float32),))
    assert rules_of(fs) == ["DP102"]
    assert "convert_element_type" in fs[0].location

    # small per-channel vectors (an amp policy's norm scale/bias
    # re-promotions) are exempt by the size floor
    fs_small = lint.lint_program(f, (SDS((64,), jnp.float32),))
    assert rules_of(fs_small) == []


def test_dp103_low_precision_large_reduction():
    # a reduce that keeps the bf16 accumulator: a raw lax-level one, or
    # (jax 0.9) jnp.sum asked for dtype=bf16
    def f(x):
        return jax.lax.reduce_sum_p.bind(x, axes=(0,))

    def f_jnp(x):
        return jnp.sum(x, dtype=jnp.bfloat16)

    for fn in (f, f_jnp):
        fs = lint.lint_program(fn, (SDS((1 << 18,), jnp.bfloat16),))
        assert "DP103" in rules_of(fs)

    # jnp's default f32 accumulation must NOT flag
    assert rules_of(lint.lint_program(
        jnp.sum, (SDS((1 << 18,), jnp.bfloat16),))) == []


def test_dp104_master_update_in_low_precision():
    def f(p, g):
        upd = p.astype(jnp.bfloat16) - 0.1 * g
        return upd.astype(jnp.float32)   # stored f32, computed bf16

    fs = lint.lint_program(
        f, (SDS((1 << 15,), jnp.float32), SDS((1 << 15,), jnp.bfloat16)))
    assert "DP104" in rules_of(fs)

    # the correct shape — upcast grads FIRST, math in f32 — is clean
    def ok(p, g):
        return p - 0.1 * g.astype(jnp.float32)

    assert rules_of(lint.lint_program(
        ok, (SDS((1 << 15,), jnp.float32),
             SDS((1 << 15,), jnp.bfloat16)))) == []


# ------------------------- collective pass -------------------------

def test_cl201_mismatched_axis():
    def f(x):
        return jax.lax.psum(x, "i")

    fs = lint.lint_program(
        f, (SDS((8,), jnp.float32),), axis_env=[("i", 2)],
        config=LintConfig(expected_axes=("dp", "tp")))
    assert rules_of(fs) == ["CL201"]
    assert "psum[0]" in fs[0].location
    assert fs[0].severity == "error"

    # matching declared mesh: clean
    assert rules_of(lint.lint_program(
        f, (SDS((8,), jnp.float32),), axis_env=[("i", 2)],
        config=LintConfig(expected_axes=("i",)))) == []


def test_cl202_psum_of_psum_and_of_pmean():
    def f(x):
        a = jax.lax.psum(jax.lax.psum(x, "i"), "i")
        b = jax.lax.psum(jax.lax.pmean(x, "i"), "i")
        return a + b

    fs = lint.lint_program(f, (SDS((8,), jnp.float32),),
                           axis_env=[("i", 2)])
    assert rules_of(fs) == ["CL202", "CL202"]


def test_cl203_scan_invariant_collective():
    def f(w, xs):
        def body(c, t):
            r = jax.lax.psum(w, "i")      # loop-invariant operand
            return c + r.sum() + t.sum(), ()

        c, _ = jax.lax.scan(body, jnp.zeros(()), xs)
        return c

    fs = lint.lint_program(f, (SDS((8,), jnp.float32),
                               SDS((4, 8), jnp.float32)),
                           axis_env=[("i", 2)])
    assert rules_of(fs) == ["CL203"]
    assert "scan" in fs[0].location

    # a carry-dependent collective must NOT flag
    def g(w, xs):
        def body(c, t):
            return c + jax.lax.psum(t, "i").sum(), ()

        c, _ = jax.lax.scan(body, jnp.zeros(()), xs)
        return c

    assert rules_of(lint.lint_program(
        g, (SDS((8,), jnp.float32), SDS((4, 8), jnp.float32)),
        axis_env=[("i", 2)])) == []


def test_cl204_fp16_psum():
    def f(x):
        return jax.lax.psum(x, "i")

    fs = lint.lint_program(f, (SDS((8,), jnp.float16),),
                           axis_env=[("i", 2)])
    assert rules_of(fs) == ["CL204"]
    # bf16 carries fp32's exponent — exempt
    assert rules_of(lint.lint_program(
        f, (SDS((8,), jnp.bfloat16),), axis_env=[("i", 2)])) == []


def test_cl205_dead_collective():
    def f(x):
        _dead = jax.lax.psum(x, "i")
        return x * 2.0

    fs = lint.lint_program(f, (SDS((8,), jnp.float32),),
                           axis_env=[("i", 2)])
    assert rules_of(fs) == ["CL205"]


def test_cl206_all_to_all_wrong_axis():
    """Expert dispatch/combine traffic off the ep axis (ISSUE 13): an
    all_to_all riding dp while the mesh carries ep is the silent
    token-scrambling transposition CL206 exists for."""
    def wrong(x):
        return jax.lax.all_to_all(x, "dp", split_axis=0, concat_axis=1,
                                  tiled=True)

    fs = lint.lint_program(wrong, (SDS((8, 8), jnp.float32),),
                           axis_env=[("dp", 2), ("ep", 2)])
    assert "CL206" in rules_of(fs)
    hit = next(f for f in fs if f.rule == "CL206")
    assert hit.severity == "error" and "all_to_all[0]" in hit.location

    # the conforming exchange — over ep — is clean
    def ok(x):
        return jax.lax.all_to_all(x, "ep", split_axis=0, concat_axis=1,
                                  tiled=True)

    assert "CL206" not in rules_of(lint.lint_program(
        ok, (SDS((8, 8), jnp.float32),),
        axis_env=[("dp", 2), ("ep", 2)]))
    # without any ep axis in sight, a dp all_to_all is legal
    assert "CL206" not in rules_of(lint.lint_program(
        wrong, (SDS((8, 8), jnp.float32),), axis_env=[("dp", 2)]))

    # a NON-dp all_to_all (the Ulysses cp head-scatter) is legitimate
    # non-expert traffic even when the mesh carries ep
    def ulysses(x):
        return jax.lax.all_to_all(x, "cp", split_axis=0, concat_axis=1,
                                  tiled=True)

    assert "CL206" not in rules_of(lint.lint_program(
        ulysses, (SDS((8, 8), jnp.float32),),
        axis_env=[("dp", 2), ("cp", 2), ("ep", 2)]))


def test_cl206_all_to_all_undeclared_axis():
    def f(x):
        return jax.lax.all_to_all(x, "zz", split_axis=0, concat_axis=1,
                                  tiled=True)

    fs = lint.lint_program(
        f, (SDS((8, 8), jnp.float32),), axis_env=[("zz", 2)],
        config=LintConfig(expected_axes=("dp", "ep")))
    assert "CL206" in rules_of(fs)


def test_cl207_incomplete_ppermute_ring():
    """A one-directional chain perm (the broken ring, ISSUE 18): rank 0
    sends but receives from nobody, so lax.ppermute silently hands it
    ZEROS — the hazard the chunked ring-overlap pipelines multiply by
    chunk count."""
    def chain(x):
        perm = [(i, i + 1) for i in range(3)]   # 4 ranks, no wrap
        return jax.lax.ppermute(x, "tp", perm)

    fs = lint.lint_program(chain, (SDS((8,), jnp.float32),),
                           axis_env=[("tp", 4)])
    hits = [f for f in fs if f.rule == "CL207"]
    assert len(hits) == 1
    assert "ZEROS" in hits[0].message and "[0]" in hits[0].message


def test_cl207_duplicate_destination():
    def dup(x):
        return jax.lax.ppermute(x, "tp", [(0, 1), (2, 1), (1, 0)])

    fs = lint.lint_program(dup, (SDS((8,), jnp.float32),),
                           axis_env=[("tp", 4)])
    assert "CL207" in rules_of(fs)
    hit = next(f for f in fs if f.rule == "CL207")
    assert "destinations" in hit.message


def test_cl207_complete_rings_clean():
    """ring_exchange / halo_exchange_1d spell complete cyclic perms —
    every sender receives — so the real overlap building blocks stay
    finding-free."""
    from apex_tpu.parallel import collectives as C

    def ring(x):
        return C.ring_exchange(x, "tp", shift=-1)

    def halo(x):
        left, right = C.halo_exchange_1d(x, "tp", halo=1, dim=0)
        return left + right

    for f in (ring, halo):
        fs = lint.lint_program(f, (SDS((8, 4), jnp.float32),),
                               axis_env=[("tp", 4)])
        assert "CL207" not in rules_of(fs), f.__name__


def test_dp105_low_precision_router_selection():
    """A bf16 router softmax feeding top_k is a finding; the
    apex_tpu.moe contract — bf16 gate GEMM operands with fp32
    accumulation, fp32 softmax + selection — is clean."""
    def bad(x, wg):
        probs = jax.nn.softmax(jnp.dot(x, wg), axis=-1)  # bf16 end-to-end
        g, _ = jax.lax.top_k(probs, 2)
        return g.sum()

    fs = lint.lint_program(
        bad, (SDS((64, 32), jnp.bfloat16), SDS((32, 8), jnp.bfloat16)))
    assert "DP105" in rules_of(fs)
    assert "top_k" in next(f for f in fs if f.rule == "DP105").location

    def good(x, wg):
        from apex_tpu.moe.router import topk_gates_dense
        out = topk_gates_dense(x, wg, 2)
        return out.gate.sum()

    assert "DP105" not in rules_of(lint.lint_program(
        good, (SDS((64, 32), jnp.bfloat16), SDS((32, 8), jnp.bfloat16))))


# ------------------------- donation pass -------------------------

def _smoke_ddp_step(donate):
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel()
    cfg = GPTConfig(vocab_size=512, seq_len=64, hidden=64, num_layers=2,
                    num_heads=4, dropout=0.0)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-3, use_pallas=False)
    state = opt.init(params)

    def loss_fn(p, b):
        return model.loss(p, b[0], b[1])

    step = ddp.make_train_step(loss_fn, opt, mesh, donate=donate,
                               batch_spec=(P("dp"), P("dp")))
    tok = SDS((8, 64), jnp.int32)
    return step, (state, None, (tok, tok))


def test_dn301_undonated_state():
    step, args = _smoke_ddp_step(donate=False)
    fs = lint.lint_step(step, args, program="undonated")
    assert rules_of(fs) == ["DN301"]
    assert "opt_state" in fs[0].location


def test_dn302_runtime_donation_cross_check():
    step, args = _smoke_ddp_step(donate=True)
    fake_report = {"donation_ok": False, "undonated_bytes": 123456,
                   "donated_bytes": 654321}
    fs = lint.lint_step(step, args, program="xchk",
                        compile_report=fake_report)
    assert rules_of(fs) == ["DN302"]
    assert fs[0].severity == "error"


def test_clean_ddp_train_step():
    """The real fused DDP step (donate=True) lints clean — the
    zero-findings contract the CI gate holds the flagships to."""
    step, args = _smoke_ddp_step(donate=True)
    fs = lint.lint_step(step, args, program="ddp")
    assert fs == []
    # the builder attached the mesh axes the collective pass used
    assert "dp" in step.mesh_axis_names


def test_clean_tp_dp_train_step():
    """The flagship builder (`make_tp_dp_train_step`, the bench
    program) lints clean at the smoke config."""
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    cfg = GPTConfig(vocab_size=512, seq_len=64, hidden=64, num_layers=2,
                    num_heads=4, dropout=0.0)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4, use_pallas=False)
    opt_state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    tok = SDS((2, 64), jnp.int32)
    fs = lint.lint_step(step, (opt_state, tok, tok), program="tp_dp")
    assert fs == []


# ------------------------- hostsync (AST) pass -------------------------

_SEEDED_SRC = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(x, y):
    if x > 0:                    # HS404
        z = float(y)             # HS402
    v = x.item()                 # HS401
    a = np.asarray(y)            # HS403
    if x.shape[0] > 2:           # static: exempt
        pass
    if y is None:                # identity test: exempt
        pass
    return x

def loss(p, b):
    return (p * b).sum()

g = jax.grad(loss)

lr = 0.0

@jax.jit
def update(p):
    return p - lr * p            # HS406: lr rebound in the loop below

def driver(p, n):
    global lr
    for i in range(n):
        lr = 0.1 * i
        f = jax.jit(lambda q: q) # HS405
        p = update(p)
    return p

def warmup(step_fn, state, batch):
    for _ in range(3):
        state, loss_v = step_fn(state, batch)
    _ = np.asarray(loss_v)       # host side: fine
    return state
'''


def test_hostsync_seeded_rules():
    fs = lint.lint_source_text(_SEEDED_SRC, "seeded.py")
    got = {(f.rule, int(f.location.split(":")[1])) for f in fs}
    assert ("HS401", 10) in got
    assert ("HS402", 9) in got
    assert ("HS403", 11) in got
    assert ("HS404", 8) in got
    assert ("HS405", 33) in got
    # host-side warmup loop syncs must NOT flag
    assert not any(loc > 38 for _, loc in got)


def test_hostsync_scalar_closure():
    src = '''
import jax

def make(n):
    lr = 0.0

    @jax.jit
    def update(p):
        return p - lr * p

    out = None
    for i in range(n):
        lr = 0.1 * i
        out = update(out)
    return out
'''
    fs = lint.lint_source_text(src, "closure.py")
    assert [f.rule for f in fs] == ["HS406"]
    assert "'lr'" in fs[0].message


def test_hostsync_fresh_def_per_iteration_exempt():
    """A def INSIDE the rebinding loop is a fresh function per
    iteration — per-iteration capture by construction, not a stale
    bake (the resnet_profile sweep shape)."""
    src = '''
import jax

def sweep(n):
    for i in range(n):
        s = i + 1

        def fb(x):
            def f(x):
                return x * s
            y, vjp = jax.vjp(f, x)
            return vjp(y)
        run(fb)
'''
    assert lint.lint_source_text(src, "sweep.py") == []


def test_hostsync_inline_disable():
    src = '''
import jax

def sweep(xs):
    for x in xs:
        f = jax.jit(lambda q: q * x)  # lint: disable=HS405
        f(x)
'''
    assert lint.lint_source_text(src, "s.py") == []
    # without the comment it fires
    assert [f.rule for f in lint.lint_source_text(
        src.replace("  # lint: disable=HS405", ""), "s.py")] == ["HS405"]


def test_repo_ast_pass_is_clean():
    """The repo-wide AST pass over apex_tpu/ itself (ISSUE 6
    satellite): the framework's own source carries no retrace/
    host-sync hazards outside inline-annotated deliberate sites."""
    fs = lint.lint_paths([str(ROOT / "apex_tpu")], root=str(ROOT))
    assert fs == [], [f"{f.rule} {f.location}" for f in fs]


# ------------------------- findings / allowlist -------------------------

def test_allowlist_parse_apply_and_glob():
    entries = lint.parse_allowlist(
        "# comment\n"
        "HS401 examples/*.py:*\n"
        "DP101\n")
    a = lint.make_finding("HS401", "examples/foo.py:12", "m")
    b = lint.make_finding("HS401", "scripts/foo.py:12", "m")
    c = lint.make_finding("DP101", "anywhere:dot_general[0]", "m")
    new, allowed = lint.apply_allowlist([a, b, c], entries)
    assert [f.location for f in new] == ["scripts/foo.py:12"]
    assert len(allowed) == 2

    with pytest.raises(ValueError, match="unknown rule"):
        lint.parse_allowlist("XX999 foo\n")


def test_committed_allowlist_is_empty():
    """ISSUE 6 satellite: every violation surfaced at introduction was
    fixed or inline-annotated — the committed gate starts empty."""
    entries = lint.load_allowlist(
        str(ROOT / "scripts" / "lint_allowlist.txt"))
    assert entries == []


def test_lint_report_schema_roundtrip():
    f = lint.make_finding("CL204", "p:psum[0]", "msg", hint="h")
    rep = lint.LintReport(target="t", new=[f], allowlisted=[])
    d = json.loads(json.dumps(rep.to_dict()))
    lint.validate_findings(d)          # round-trips
    assert d["ok"] is False
    text = lint.render_findings(d)
    assert "CL204" in text and "fix: h" in text

    bad = dict(d, lint_schema_version=999)
    with pytest.raises(ValueError, match="lint_schema_version"):
        lint.validate_findings(bad)
    with pytest.raises(ValueError, match="ok bit"):
        lint.validate_findings(dict(d, ok=True))


def test_unknown_rule_and_severity_rejected():
    with pytest.raises(ValueError, match="unknown lint rule"):
        lint.Finding(rule="ZZ000", severity="error", location="x",
                     message="m")
    with pytest.raises(ValueError, match="severity"):
        lint.Finding(rule="HS401", severity="fatal", location="x",
                     message="m")


# ------------------------- integration -------------------------

def test_analyze_step_attaches_lint():
    """monitor.analyze_step(lint=True): findings ride on the
    CompileReport — and from there into the flight-recorder crash
    dump."""
    from apex_tpu import monitor

    step, args = _smoke_ddp_step(donate=True)
    rep = monitor.analyze_step(step, args, lint=True)
    assert rep.lint is not None
    assert rep.lint["ok"] is True and rep.lint["findings"] == []
    assert rep.to_dict()["lint"]["ok"] is True
    assert "lint: clean" in monitor.render_budget_table(rep)

    # a lint=False report carries None (and renders without the line)
    rep2 = monitor.analyze_step(step, args)
    assert rep2.lint is None
    assert "lint" not in monitor.render_budget_table(rep2)


def _run_script(path, *args, timeout=600):
    return subprocess.run(
        [sys.executable, str(path), *args], capture_output=True,
        text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_lint_step_selftest():
    """Tier-1 CI gate (mirrors `flight_report.py --selftest`): the
    committed fixture validates + renders under the CURRENT schema."""
    r = _run_script(ROOT / "scripts" / "lint_step.py", "--selftest")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint_step --selftest: OK" in r.stdout


def test_lint_step_cli_flagships_clean():
    """The acceptance gate: `scripts/lint_step.py` exits 0 on the
    flagship GPT/BERT/serve/MoE step functions with the EMPTY
    committed allowlist (the MoE step is the ISSUE 13 acceptance
    criterion: its ep all_to_alls and fp32 router must clear the
    CL206/DP105 rules built for them)."""
    r = _run_script(ROOT / "scripts" / "lint_step.py", "gpt", "bert",
                    "serve", "moe")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CLEAN" in r.stdout
