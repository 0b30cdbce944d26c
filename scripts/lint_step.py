"""Static lint gate for the flagship train steps (ISSUE 6).

usage:
  python scripts/lint_step.py [targets...]      # default: gpt bert resnet ast
  python scripts/lint_step.py --selftest        # fixture schema-drift gate
  python scripts/lint_step.py --ast PATH...     # source pass over trees
  python scripts/lint_step.py --json            # machine-readable reports

Builds the EXACT flagship GPT-350M / BERT-Large / ResNet-50 train
steps (the bench.py programs; on a CPU backend the smoke-size configs
substitute, same build path), traces them WITHOUT compiling or
executing, and runs `apex_tpu.lint`'s program passes (dtype-policy,
collectives, donation) plus the repo-wide AST retrace/host-sync pass
over apex_tpu/, examples/, scripts/ and bench.py.  Exit is nonzero on
any finding not accepted by the committed allowlist
(scripts/lint_allowlist.txt) — the CI gate ZeRO-3 and the TP-overlap
work are developed against.

`--selftest` renders the committed fixture (scripts/lint_fixture.json)
through `lint.validate_findings` + `lint.render_findings` and exits
nonzero when the finding schema drifted or the rendering lost its
load-bearing markers (mirrors `flight_report.py --selftest`); run from
the tier-1 suite (tests/test_lint.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# scripts/ itself, for the shared gpt_anatomy._build_bench_step builder
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

# tracing is host-side: the gate runs on the CPU unless the operator
# explicitly asked for device truth
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the moe target needs a dp x ep mesh: on the CPU backend force a
# 4-way virtual mesh (must precede the first jax import, conftest-
# style; the other targets build single-device meshes and are
# unaffected by extra visible devices)
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
ALLOWLIST = os.path.join(_HERE, "lint_allowlist.txt")
FIXTURE = os.path.join(_HERE, "lint_fixture.json")

# markers the fixture rendering must contain; losing one means the
# renderer no longer tells the story the fixture encodes
_FIXTURE_MARKERS = (
    "=== lint: fixture-step ===",
    "ERROR   CL201",
    "ERROR   CL206",
    "WARNING DP101",
    "WARNING DP105",
    "HS401 examples/broken.py:12",
    "fix: cast the operands",
    "5 new finding(s), 3 error(s)",
    "(1 allowlisted finding(s) accepted)",
)

# AST-pass trees (repo-relative) the default gate walks
AST_TREES = ("apex_tpu", "examples", "scripts", "bench.py", "tests")


def selftest() -> int:
    from apex_tpu import lint

    with open(FIXTURE) as f:
        rep = json.load(f)
    try:
        lint.validate_findings(rep)
        text = lint.render_findings(rep)
    except ValueError as e:
        print(f"lint_step --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? update scripts/lint_fixture.json to "
              "the new schema)", file=sys.stderr)
        return 1
    missing = [m for m in _FIXTURE_MARKERS if m not in text]
    if missing:
        print(text)
        print(f"lint_step --selftest: rendering lost expected "
              f"markers: {missing}", file=sys.stderr)
        return 1
    print(text)
    print("lint_step --selftest: OK")
    return 0


def _build_gpt(on_tpu):
    """The flagship GPT-350M step — gpt_anatomy's shared builder (the
    EXACT bench program; one copy, not a drift-prone re-spelling)."""
    import gpt_anatomy

    _, step, args, _ = gpt_anatomy._build_bench_step(
        "350m", on_tpu, mode="lint")
    return step, args


def _build_bert(on_tpu):
    """The flagship BERT-Large MLM+NSP step with FusedLAMB — same
    shared builder."""
    import gpt_anatomy

    _, step, args, _ = gpt_anatomy._build_bench_step(
        "bert", on_tpu, mode="lint")
    return step, args


def _build_resnet(on_tpu):
    """The flagship ResNet AMP-O1 step (ddp.make_train_step path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models.resnet import ResNet
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    from apex_tpu.optimizers.fused_sgd import FusedSGD
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M

    batch, size, arch = (256, 224, "resnet50") if on_tpu else \
        (4, 32, "resnet18")
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = ResNet(arch, num_classes=1000, axis_name="dp",
                   stem="space_to_depth" if on_tpu else "conv7")
    params, mstate = model.init(jax.random.PRNGKey(0))
    amp_state = amp.initialize(opt_level="O1")

    def loss_fn(p, ms, b):
        x, y = b
        logits, new_ms = model.apply(p, ms, x, training=True)
        return jnp.mean(softmax_cross_entropy_loss(
            logits.astype(jnp.float32), y)), new_ms

    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = opt.init(params)
    scaler = amp_state.loss_scalers[0]
    step = ddp.make_train_step(loss_fn, opt, mesh, amp_state=amp_state,
                               batch_spec=(P("dp"), P("dp")),
                               with_state=True)
    x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return step, (state, scaler, mstate, (x, y))


def _build_serve(on_tpu):
    """The flagship serving DECODE step (apex_tpu.serve, ISSUE 8): the
    continuous-batching program that must stay HS4xx-clean — a host
    sync inside it would serialize every concurrent stream.  Built via
    the shared serve builder (the exact bench/example program); the
    smoke slot count keeps the CPU trace fast while exercising the
    full paged-attention + state-update jaxpr."""
    from apex_tpu.serve import build_flagship_engine

    eng = build_flagship_engine(on_tpu)
    return eng.decode_step, (eng.params, eng.kv, eng.state)


def _build_moe(on_tpu):
    """The flagship expert-parallel MoE-GPT step (apex_tpu.moe, ISSUE
    13): dp x ep mesh over all visible devices, ZeRO-2 master state
    sharded over the combined data axes, dispatch/combine all_to_alls
    over ep — the program the CL206/DP105 rules exist to hold.  Built
    via the shared builder (the exact bench program)."""
    from apex_tpu.models.moe_gpt import build_moe_train_step

    _, step, args, _ = build_moe_train_step(on_tpu)
    return step, args


BUILDERS = {"gpt": _build_gpt, "bert": _build_bert,
            "resnet": _build_resnet, "serve": _build_serve,
            "moe": _build_moe}


def main() -> int:
    ap = argparse.ArgumentParser(
        description="static lint gate for the flagship train steps")
    ap.add_argument("targets", nargs="*",
                    help=f"subset of {sorted(BUILDERS)} + 'ast' "
                         "(default: all)")
    ap.add_argument("--selftest", action="store_true",
                    help="render the committed fixture; exit 1 on "
                         "schema drift")
    ap.add_argument("--ast", nargs="+", metavar="PATH", default=None,
                    help="ONLY run the AST pass over these paths")
    ap.add_argument("--allowlist", default=ALLOWLIST,
                    help="allowlist file (default: the committed one)")
    ap.add_argument("--json", action="store_true",
                    help="print LintReport JSON lines instead of text")
    args = ap.parse_args()
    if args.selftest:
        return selftest()

    from apex_tpu import lint

    allowlist = (lint.load_allowlist(args.allowlist)
                 if os.path.exists(args.allowlist) else [])

    reports = []
    if args.ast is not None:
        targets = []
        ast_paths = args.ast
    else:
        targets = args.targets or sorted(BUILDERS) + ["ast"]
        bad = [t for t in targets if t != "ast" and t not in BUILDERS]
        if bad:
            ap.error(f"unknown target(s) {bad}; choices: "
                     f"{sorted(BUILDERS) + ['ast']}")
        ast_paths = ([os.path.join(_ROOT, t) for t in AST_TREES]
                     if "ast" in targets else [])

    from apex_tpu.ops._common import on_chip
    on_tpu = on_chip()
    for t in targets:
        if t == "ast":
            continue
        step, step_args = BUILDERS[t](on_tpu)
        findings = lint.lint_step(step, step_args, program=t)
        new, allowed = lint.apply_allowlist(findings, allowlist)
        reports.append(lint.LintReport(target=t, new=new,
                                       allowlisted=allowed))
        from apex_tpu.parallel import mesh as M
        M.destroy_model_parallel()
    if ast_paths:
        findings = lint.lint_paths(ast_paths, root=_ROOT)
        new, allowed = lint.apply_allowlist(findings, allowlist)
        reports.append(lint.LintReport(target="ast", new=new,
                                       allowlisted=allowed))

    rc = 0
    for rep in reports:
        if args.json:
            print(json.dumps(rep.to_dict()))
        else:
            print(lint.render_findings(rep))
            print()
        if not rep.ok:
            rc = 1
    if not args.json:
        verdict = "CLEAN" if rc == 0 else "FINDINGS — gate fails"
        print(f"lint_step: {len(reports)} target(s), {verdict}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
