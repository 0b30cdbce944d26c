"""Comms/overlap CI gate for the flagship train steps (ISSUE 7).

usage:
  python scripts/comms_probe.py [targets...]   # default: gpt_zero2 gpt
  python scripts/comms_probe.py --selftest     # fixture schema-drift gate
  python scripts/comms_probe.py --report PATH  # gate a saved CommsReport JSON
  python scripts/comms_probe.py --json         # machine-readable reports

Builds each flagship step (the EXACT bench programs; on a CPU backend
the smoke configs substitute, same build path), AOT lowers+compiles it
WITHOUT executing, and runs `apex_tpu.monitor.comms`' collective
inventory + overlap analysis.  Exit is nonzero when a collective the
analyzer expects to overlap (async, >= 1 MiB, all-reduce/all-gather/
reduce-scatter) SERIALIZED — its start→done window held zero dot
flops — and is not accepted by the committed allowlist
(scripts/comms_allowlist.txt, COMMITTED EMPTY).  This is the standing
gate the ZeRO-3 and TP-overlap work (ROADMAP items 1-2) are developed
against: a chunked-overlap regression shows up here before it shows up
as a flat tokens/s round.

On backends that emit no async collectives (CPU: XLA lowers sync
all-reduces only) the overlap plane is unmeasurable and the gate
passes with a note — the inventory and roofline still print.  The
`--report` mode gates a SAVED report JSON instead (e.g. one produced
on real hardware, or the committed fixture — which contains a seeded
serialized collective and therefore exits nonzero, the gate's own
negative control).

`--selftest` validates + renders the committed fixture
(scripts/comms_fixture.json) and exits nonzero when the schema
drifted, the rendering lost its load-bearing markers, or the seeded
serialized collective is NOT flagged (mirrors `lint_step.py
--selftest`); run from the tier-1 suite (tests/test_comms.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# scripts/ itself, for the shared gpt_anatomy._build_bench_step builder
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

# the audit is AOT: the gate runs on the CPU unless the operator
# explicitly asked for device truth.  `--backend tpu` (or
# an explicit JAX_PLATFORMS) IS that ask — the overlap plane only
# exists in a TPU schedule, so the on-hardware runbook needs a spelled
# way in; must be resolved before the first jax import, hence argv
# peeking rather than argparse
if "--backend" in sys.argv[1:]:
    try:
        os.environ["JAX_PLATFORMS"] = \
            sys.argv[sys.argv.index("--backend") + 1]
    except IndexError:
        sys.exit("--backend needs a value (e.g. --backend tpu)")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the ZeRO-2 target needs a dp axis: on the CPU backend force a 2-way
# virtual mesh (must precede the first jax import, conftest-style)
if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
ALLOWLIST = os.path.join(_HERE, "comms_allowlist.txt")
FIXTURE = os.path.join(_HERE, "comms_fixture.json")

# markers the fixture rendering must contain; losing one means the
# renderer no longer tells the story the fixture encodes
_FIXTURE_MARKERS = (
    "=== comms: fixture-step ===",
    "| all-reduce",
    "| reduce-scatter",
    "| all-to-all",
    "| collective-permute",
    "| ep ",
    "| tp ",
    "**SER**",
    "SERIALIZED collective(s)",
    "roofline: predicted comm",
)

# the seeded serialized-chunk negative control (ISSUE 18): one chunk
# of the fixture's chunked-TP ring pair is seeded serialized and must
# stay flagged BY NAME, or the gate is blind to ring-hop regressions
_SEEDED_SERIALIZED_CHUNK = "collective-permute-start.8"


def selftest() -> int:
    from apex_tpu.monitor import comms

    with open(FIXTURE) as f:
        rep = json.load(f)
    try:
        comms.validate_comms_report(rep)
        text = comms.render_comms_table(rep, label="fixture-step")
    except ValueError as e:
        print(f"comms_probe --selftest: SCHEMA DRIFT — {e}",
              file=sys.stderr)
        print("(bump-side change? update scripts/comms_fixture.json to "
              "the new schema)", file=sys.stderr)
        return 1
    missing = [m for m in _FIXTURE_MARKERS if m not in text]
    if missing:
        print(text)
        print(f"comms_probe --selftest: rendering lost expected "
              f"markers: {missing}", file=sys.stderr)
        return 1
    ser = comms.serialized_collectives(rep)
    if not ser:
        print("comms_probe --selftest: the fixture's seeded serialized "
              "collective is no longer flagged — the gate is blind",
              file=sys.stderr)
        return 1
    if _SEEDED_SERIALIZED_CHUNK not in {c["name"] for c in ser}:
        print("comms_probe --selftest: the seeded serialized ring "
              f"CHUNK ({_SEEDED_SERIALIZED_CHUNK}) is no longer "
              "flagged — the gate is blind to chunked-overlap "
              "regressions", file=sys.stderr)
        return 1
    # the chunked-shape pin: the fixture's ring pair must stay
    # chunk-count-many EQUAL-payload hops (2 x 2 MiB = the displaced
    # monolithic all-gather shard) — the inventory shape the live
    # gpt_tp_overlap gate pins against the chunks=1 spelling
    chunk_pool = [c for c in rep["collectives"]
                  if c["kind"] == "collective-permute"]
    payloads = {c["operand_bytes"] for c in chunk_pool}
    if len(chunk_pool) != 2 or payloads != {2097152}:
        print("comms_probe --selftest: the fixture's chunked ring "
              f"pair drifted (n={len(chunk_pool)}, "
              f"payloads={sorted(payloads)}; want 2 x 2097152 B)",
              file=sys.stderr)
        return 1
    print(text)
    print("comms_probe --selftest: OK")
    return 0


def _build_gpt_zero2(on_tpu):
    """The flagship ZeRO-2 data-parallel GPT step: DistributedFusedAdam
    (n_buckets=4, per-bucket psum_scatter grad sync) through
    `ddp.make_train_step` — the program whose per-bucket reduce-scatter
    / backward overlap this gate exists to hold.  dp = every visible
    device (the CPU backend is forced to a 2-way virtual mesh above);
    on TPU the real 350M bench config, on CPU the smoke config."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.parallel import ddp
    from apex_tpu.parallel import mesh as M

    if on_tpu:
        batch, seq = 12, 1024
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                        num_layers=24, num_heads=16, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        remat=False, use_flash_attention=True)
    else:
        seq = 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel()
    dp = mesh.devices.size
    if not on_tpu:
        # the batch must shard over however many virtual devices the
        # caller's env forced (the tier-1 conftest pins 8)
        batch = max(4, dp)
    # ddp.make_train_step shard_maps the batch over dp (P("dp")) —
    # round up so the gate runs on any topology, not just ones that
    # happen to divide the bench batch
    batch = -(-batch // dp) * dp
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedFusedAdam(
        num_shards=dp, lr=1e-4, n_buckets=4, use_pallas=on_tpu or None,
        master_dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    sspec = opt.state_partition_specs()
    state = jax.jit(shard_map(opt.init, mesh=mesh, in_specs=(P(),),
                              out_specs=sspec, check_vma=False))(params)

    def loss_fn(p, b):
        return model.loss(p, b[0], b[1])

    step = ddp.make_train_step(loss_fn, opt, mesh,
                               batch_spec=(P("dp"), P("dp")))
    del params
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return step, (state, None, (tokens, labels))


def _build_anatomy(target):
    """A tp_dp flagship step via gpt_anatomy's shared bench builder."""

    import gpt_anatomy

    on_tpu = _on_chip()
    _, step, args, _ = gpt_anatomy._build_bench_step(
        target, on_tpu, mode="comms")
    return step, args


def _build_gpt_tp_overlap(on_tpu, chunks=2):
    """The flagship CHUNKED-TP GPT step (ISSUE 18): tp=2
    sequence-parallel GPT with `overlap_chunks` forced (bypassing the
    tuner so the inventory is deterministic on untuned machines) —
    the column-parallel all-gather+GEMM decomposed into a ppermute
    ring interleaved with partial GEMMs, the row-parallel
    reduce-scatter chunked along the sequence.  The gate pins the
    chunked program's collective inventory against the monolithic
    (chunks=1) spelling of the SAME model: chunk-count-many smaller
    collectives, displaced all-gather bytes reappearing as equal ring
    ppermute traffic.  dp takes the remaining devices; on TPU the
    350M bench config, on CPU the smoke config."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    if on_tpu:
        batch, seq = 12, 1024
        cfg = GPTConfig(vocab_size=50304, seq_len=seq, hidden=1024,
                        num_layers=24, num_heads=16, dropout=0.0,
                        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                        remat=False, use_flash_attention=True,
                        sequence_parallel=True,
                        overlap_chunks=chunks)
    else:
        batch, seq = 2, 64
        cfg = GPTConfig(vocab_size=512, seq_len=seq, hidden=64,
                        num_layers=2, num_heads=4, dropout=0.0,
                        sequence_parallel=True,
                        overlap_chunks=chunks)
    _build_gpt_tp_overlap.layers = cfg.num_layers
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
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return step, (opt_state, tokens, labels)


def _build_serve():
    """The flagship serving DECODE step (apex_tpu.serve, ISSUE 8).
    Single-chip serving emits ZERO collectives — this target is the
    standing negative control: any collective appearing in the decode
    inventory is a regression (an accidental cross-slot reduction
    would serialize every concurrent stream), and a future
    tensor-parallel serving path must move it OFF this gate into an
    allowlist-reviewed pattern, the PR 7 NOTE workflow."""

    from apex_tpu.serve import build_flagship_engine

    on_tpu = _on_chip()
    eng = build_flagship_engine(on_tpu)
    return eng.decode_step, (eng.params, eng.kv, eng.state)


def _build_moe():
    """The flagship expert-parallel MoE-GPT step (apex_tpu.moe, ISSUE
    13): meshed over ALL visible devices (ep = 2 on any even device
    count, dp = world/ep; batch rounded to a dp x ep multiple by the
    builder), ZeRO-2 state over the combined data axes.  The
    inventory must show the dispatch/combine all-to-alls over ['ep']
    priced by the ring formula ((n-1)/n * D / bw) — the seeded
    pattern in scripts/comms_fixture.json — next to the per-bucket
    reduce-scatters over the combined grad-sync axes."""

    from apex_tpu.models.moe_gpt import build_moe_train_step

    on_tpu = _on_chip()
    _, step, args, _ = build_moe_train_step(on_tpu)
    return step, args


def _on_chip():
    from apex_tpu.ops._common import on_chip
    return on_chip()


BUILDERS = {
    "gpt_zero2": lambda: _build_gpt_zero2(_on_chip()),
    "gpt": lambda: _build_anatomy("350m"),
    "bert": lambda: _build_anatomy("bert"),
    "serve": _build_serve,
    "moe": _build_moe,
    "gpt_tp_overlap": lambda: _build_gpt_tp_overlap(_on_chip()),
}
DEFAULT_TARGETS = ("gpt_zero2", "gpt", "serve", "moe",
                   "gpt_tp_overlap")

# the chunked-TP flagship's shape knobs, shared with the inventory pin
# (kept in one place so the expected-count formula and the builder
# can't drift apart)
_TP_OVERLAP_TP = 2
_TP_OVERLAP_CHUNKS = 2


def _pin_tp_overlap_inventory(chunked, mono, layers, as_json) -> int:
    """Pin the chunked-TP program's collective inventory against the
    monolithic (chunks=1) spelling of the SAME model — the ISSUE 18
    contract: chunk-count-many smaller collectives, same total bytes
    (± padding).  Measured invariants (tp=p, c=chunks, L layers):

      * the monolithic program emits ZERO collective-permutes; the
        chunked one emits exactly 2·(2L)·(p−1)·c ring hops — (fwd
        ring + wgrad ring) × (qkv, fc1 per layer) × (p−1) hops ×
        c chunks — all carrying the SAME per-hop payload (every ring
        moves x-chunks, so hop sizes are uniform),
      * reduce-scatter bytes are conserved (c× more, each c× smaller),
      * the displaced all-gather bytes reappear as ring traffic:
        cp_bytes == 2 × (ag_bytes_mono − ag_bytes_chunked) — the
        factor 2 is the wgrad ring re-moving what the fwd ring moved
        (the monolithic spelling saves gathered x as a residual
        instead; chunking trades those bytes for overlap + memory),
      * the dp grad-sync plane (all-reduce) is byte-identical —
        chunking must not leak into the data-parallel collectives.
    """
    p, c = _TP_OVERLAP_TP, _TP_OVERLAP_CHUNKS
    fails = []
    cp = [x for x in chunked["collectives"]
          if x["kind"] == "collective-permute"]
    if mono["counts"].get("collective-permute", 0):
        fails.append("monolithic (chunks=1) spelling emits "
                     "collective-permute — the chunks=1 path is no "
                     "longer the pre-overlap program")
    want = 2 * (2 * layers) * (p - 1) * c
    if len(cp) != want:
        fails.append(f"ring ppermute count {len(cp)} != expected "
                     f"{want} (= 2 rings x {2 * layers} col sites x "
                     f"{p - 1} hops x {c} chunks)")
    sizes = sorted({x["operand_bytes"] for x in cp})
    if len(sizes) > 1:
        fails.append(f"ring hop payloads not uniform: {sizes}")
    ag_m = mono["bytes_by_kind"].get("all-gather", 0)
    ag_c = chunked["bytes_by_kind"].get("all-gather", 0)
    cp_b = chunked["bytes_by_kind"].get("collective-permute", 0)
    displaced = ag_m - ag_c
    if displaced <= 0 or cp_b <= 0 or \
            abs(cp_b - 2 * displaced) > 0.05 * max(cp_b, 1):
        fails.append(f"displaced all-gather bytes ({displaced}) != "
                     f"ring bytes/2 ({cp_b}/2) beyond padding")
    rs_m = mono["bytes_by_kind"].get("reduce-scatter", 0)
    rs_c = chunked["bytes_by_kind"].get("reduce-scatter", 0)
    if abs(rs_c - rs_m) > 0.05 * max(rs_m, 1):
        fails.append(f"reduce-scatter bytes not conserved: "
                     f"{rs_m} -> {rs_c}")
    if chunked["bytes_by_kind"].get("all-reduce", 0) != \
            mono["bytes_by_kind"].get("all-reduce", 0):
        fails.append("chunking leaked into the dp all-reduce plane")
    if as_json:
        print(json.dumps({"target": "gpt_tp_overlap_inventory_pin",
                          "n_ring_hops": len(cp),
                          "expected_ring_hops": want,
                          "ring_bytes": cp_b,
                          "displaced_all_gather_bytes": displaced,
                          "fails": fails, "ok": not fails}))
    else:
        print(f"inventory pin (chunks={c} vs monolithic): "
              f"{len(cp)} ring hop(s) of {sizes[0] if sizes else 0} B "
              f"replace {displaced} displaced all-gather byte(s)")
        for f in fails:
            print(f"inventory pin: FAIL — {f}")
        print(f"inventory pin: {'FAIL' if fails else 'PASS'}")
        print()
    return 1 if fails else 0


def _gate_report(rep_dict, target, allowlist, as_json) -> int:
    from apex_tpu.monitor import comms

    ser = comms.serialized_collectives(rep_dict)
    new, allowed = comms.apply_allowlist(ser, allowlist, target)
    if as_json:
        print(json.dumps({"target": target, "report": rep_dict,
                          "new": new, "allowlisted": allowed}))
    else:
        print(comms.render_comms_table(rep_dict, label=target))
        if allowed:
            print(f"({len(allowed)} allowlisted serialized "
                  f"collective(s) accepted)")
        if not rep_dict.get("async_supported"):
            print("gate: PASS (overlap not measurable on this backend)")
        elif new:
            print(f"gate: FAIL — {len(new)} serialized collective(s) "
                  "not in scripts/comms_allowlist.txt")
        else:
            print("gate: PASS")
        print()
    return 1 if new else 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="comms/overlap CI gate for the flagship train steps")
    ap.add_argument("targets", nargs="*",
                    help=f"subset of {sorted(BUILDERS)} "
                         f"(default: {list(DEFAULT_TARGETS)})")
    ap.add_argument("--selftest", action="store_true",
                    help="validate + render the committed fixture; "
                         "exit 1 on schema drift")
    ap.add_argument("--report", metavar="PATH", default=None,
                    help="gate a saved CommsReport JSON instead of "
                         "building steps")
    ap.add_argument("--backend", metavar="NAME", default=None,
                    help="JAX_PLATFORMS for the build (e.g. tpu); "
                         "consumed before the first jax import by the "
                         "argv peek above — registered here so argparse "
                         "accepts it")
    ap.add_argument("--allowlist", default=ALLOWLIST,
                    help="allowlist file (default: the committed one)")
    ap.add_argument("--json", action="store_true",
                    help="print JSON instead of tables")
    args = ap.parse_args()
    if args.selftest:
        return selftest()

    from apex_tpu.monitor import comms

    allowlist = []
    if os.path.exists(args.allowlist):
        with open(args.allowlist) as f:
            allowlist = comms.parse_allowlist(f.read())

    if args.report is not None:
        with open(args.report) as f:
            rep = json.load(f)
        comms.validate_comms_report(rep)
        return _gate_report(
            rep, os.path.basename(args.report), allowlist, args.json)

    targets = args.targets or list(DEFAULT_TARGETS)
    bad = [t for t in targets if t not in BUILDERS]
    if bad:
        ap.error(f"unknown target(s) {bad}; choices: {sorted(BUILDERS)}")

    from apex_tpu.parallel import mesh as M

    rc = 0
    for t in targets:
        step, step_args = BUILDERS[t]()
        rep = comms.comms_report(step, step_args)
        rc |= _gate_report(rep.to_dict(), t, allowlist, args.json)
        if t == "gpt_tp_overlap":
            # the chunked target carries a second gate: its inventory
            # pinned against the monolithic spelling of the same model

            on_tpu = _on_chip()
            mono_step, mono_args = _build_gpt_tp_overlap(
                on_tpu, chunks=1)
            mono = comms.comms_report(mono_step, mono_args)
            rc |= _pin_tp_overlap_inventory(
                rep.to_dict(), mono.to_dict(),
                _build_gpt_tp_overlap.layers, args.json)
        M.destroy_model_parallel()
    if not args.json:
        verdict = "CLEAN" if rc == 0 else "SERIALIZED — gate fails"
        print(f"comms_probe: {len(targets)} target(s), {verdict}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
