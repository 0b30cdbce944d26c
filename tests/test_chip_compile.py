"""Compile-for-the-chip tests: the TPU's compiler is installed here and
compiles for a v5e that is described, not attached.  Each case compiles
a kernel of the main path (or the whole flagship step) at its real
width and asserts that Mosaic's `tpu_custom_call` is in the program —
what interpret mode on the CPU mesh cannot show (a slice not aligned to
the tiling, too much VMEM, a program that does not fit the chip).

A compile that passes is not a chip run; `chip_smoke.py` is.

This is the only file that describes the chip.  The topology is
described inside a module-scoped fixture, never at import time: one
process at a time may load the TPU's library, and every xdist worker
imports every test file.  The kernels ask `ops._common.on_chip()`,
which sees the CPU during such a compile, so each test steers it with
monkeypatch — no option of the program.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke
from apex_tpu.ops import _common
from apex_tpu.ops import optimizer_kernels as K

GIB = 2 ** 30
HBM_BYTES = 15.75 * GIB   # what one v5e chip gives a program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Kernel dispatch and interpret mode as they read on the chip."""
    monkeypatch.setattr(_common, "on_chip", lambda: True)


def _compile(fn, args, sharding):
    """Compile jit(fn) for the described chip from the shapes of
    `args` (arrays or an eval_shape result)."""
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn).lower(*sds).compile()


def _n_kernels(compiled):
    return compiled.as_text().count("tpu_custom_call")


def _bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------- the programs chip_smoke.py runs -------------------

def _smoke_case(name, device):
    (case,) = [c for c in chip_smoke.kernel_cases(device) if c.name == name]
    if name == "flash_mla_192_128":
        # on the chip the tuner picks the single-pass backward at
        # (1024, 512) blocks; here it sees a CPU, so the test hands the
        # committed v5e config over itself
        from apex_tpu import tune
        from apex_tpu.tune import defaults
        key = tune.make_key("flash_sdpa", tune.flash_attrs(
            2, 32, 4096, 4096, 192, "bfloat16", True, dv=128))
        config = dict(defaults.DEFAULTS["v5e"][key]["config"])
        config["fused_backward"] = config.pop("fused_bwd")
        assert config["fused_backward"] is True
        case = chip_smoke.flash_case(name, (2, 32, 4096, 192), config,
                                     v_dim=128)
    if name == "gqa_flash_grads":
        # the same for the grouped-query call: the single pass, which
        # sums dk and dv over a group inside its grid
        from apex_tpu import tune
        from apex_tpu.tune import defaults
        key = tune.make_key("flash_sdpa", tune.flash_attrs(
            1, 64, 4096, 4096, 128, "bfloat16", True, hkv=8))
        config = dict(defaults.DEFAULTS["v5e"][key]["config"])
        config["fused_backward"] = config.pop("fused_bwd")
        assert config["fused_backward"] is True
        case = chip_smoke.gqa_flash_case(name, 1, 64, 8, 4096, 128, config)
    return case


@pytest.mark.parametrize("name,min_kernels", [
    ("gqa_flash_grads", 2), ("delta_rule_grads", 3),
    ("flash_nope_192_128_docs", 3), ("delta_rule_docs_grads", 3),
    ("conv_stage_grads", 4),
    ("flash_350m", 2), ("flash_qkv_350m", 2), ("flash_d64_s2048", 2),
    ("flash_mla_192_128", 2), ("mla_attention_grads", 6),
    ("moe_held_experts", 6), ("moe_held_experts_320", 6),
    ("short_conv_grads", 0), ("gqa_flash_d64_grads", 2),
    ("moe_held_experts_half", 6),
    ("adam_flat_fp32", 1),
    ("adam_flat_bf16", 1), ("xent_pallas", 2), ("xent_vocab_parallel", 0),
    ("flash_decode", 1)])
def test_smoke_kernel_check_compiles_and_fits(name, min_kernels, topo,
                                              one_chip, on_chip):
    """Each kernel phase of chip_smoke.py — kernel, reference and
    comparison in one program — compiles for the chip with its kernels
    in it and fits the chip's memory."""
    case = _smoke_case(name, topo.devices[0])
    args = jax.eval_shape(case.make_args, jax.random.PRNGKey(0))
    compiled = _compile(chip_smoke.kernel_check(case), args, one_chip)
    assert _n_kernels(compiled) >= min_kernels
    assert (_n_kernels(compiled) > 0) == case.mosaic
    assert _bytes(compiled) < HBM_BYTES


# ------------------------ kernels, one at a time ------------------------

def _flash(direction):
    from apex_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    if direction == "fwd":
        return fwd
    return jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(12, 16, 1024, 64), (7, 32, 512, 64)],
                         ids=["gpt350m", "gpt1p3b"])
def test_flash_attention_compiles(shape, direction, one_chip, on_chip):
    q = _sds(shape, jnp.bfloat16)
    compiled = _compile(_flash(direction), (q, q, q), one_chip)
    assert _n_kernels(compiled) >= 1


def _bert_large_lamb():
    """BERT-Large's flat LAMB layout and bf16 state, as bench.py trains
    it: (spec, flat buffer shape)."""
    from apex_tpu.models.bert import Bert, BertConfig
    from apex_tpu.optimizers import flat as F

    params = jax.eval_shape(Bert(BertConfig(dtype=jnp.bfloat16)).init,
                            jax.random.PRNGKey(0))
    spec = F.make_spec(params, align=K._LANES)
    n = -(-spec.total // K.FLAT_TILE) * K.FLAT_TILE
    assert n > 330e6
    return spec, _sds((n,), jnp.bfloat16)


def _lamb_phase1(spec, buf):
    return (functools.partial(
        K.lamb_phase1_flat, clip_ratio=1.0, step=10, beta1=0.9,
        beta2=0.999, eps=1e-6, weight_decay=0.01), (buf,) * 4)


def _lamb_norms(spec, buf):
    return (lambda x: K.per_tensor_l2norm_aligned(x, spec), (buf,))


def _lamb_phase2(spec, buf):
    ratio = _sds((len(spec.sizes),), jnp.float32)
    return (lambda p, u, r: K.lamb_phase2_seg(p, u, r, spec, 1e-3),
            (buf, buf, ratio))


@pytest.mark.parametrize("build", [_lamb_phase1, _lamb_norms, _lamb_phase2],
                         ids=["phase1", "trust_ratio_norms", "phase2"])
def test_lamb_compiles_at_bert_large(build, one_chip, on_chip):
    fn, args = build(*_bert_large_lamb())
    assert _n_kernels(_compile(fn, args, one_chip)) >= 1


def _xent(direction):
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    if direction == "fwd":
        return softmax_cross_entropy_loss
    return jax.grad(lambda x, t: softmax_cross_entropy_loss(x, t).sum())


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_xent_compiles_at_gpt_vocab(direction, one_chip, on_chip):
    args = (_sds((12288, 50304), jnp.bfloat16), _sds((12288,), jnp.int32))
    assert _n_kernels(_compile(_xent(direction), args, one_chip)) >= 1


def test_causal_softmax_compiles_at_serve_reference_shape(one_chip, on_chip):
    """The dense-attention GPT forward chip_smoke.py's serve phase is
    held to: 8 prompts x 16 heads of 128 x 128 scores."""
    from apex_tpu.ops.softmax import scaled_upper_triang_masked_softmax
    args = (_sds((8 * 16, 128, 128), jnp.bfloat16),)
    compiled = _compile(
        lambda x: scaled_upper_triang_masked_softmax(x, 0.125), args,
        one_chip)
    assert _n_kernels(compiled) >= 1


def test_opt_in_kernels_compile(one_chip, on_chip):
    """LayerNorm (fwd + bwd) and the fused dense + GELU are off the
    default dispatch (XLA's own fusion won on the chip, docs/PERF.md)
    but stay reachable by override: Mosaic must still accept them."""
    from apex_tpu.ops.fused_dense import linear_bias
    from apex_tpu.ops.layer_norm import fused_layer_norm

    x = _sds((12288, 1024), jnp.bfloat16)
    w = _sds((1024,), jnp.float32)

    def ln(x, w, b):
        return fused_layer_norm(x, w, b, use_pallas_override=True)

    ln_grad = jax.grad(lambda *a: ln(*a).astype(jnp.float32).sum(),
                       argnums=(0, 1, 2))
    assert _n_kernels(_compile(ln, (x, w, w), one_chip)) >= 1
    assert _n_kernels(_compile(ln_grad, (x, w, w), one_chip)) >= 1
    dense = _compile(
        lambda x, w, b: linear_bias(x, w, b, "gelu",
                                    use_pallas_override=True),
        (x, _sds((1024, 4096), jnp.bfloat16), _sds((4096,), jnp.bfloat16)),
        one_chip)
    assert _n_kernels(dense) >= 1


# --------------------------- the whole program ---------------------------

def flagship_step(devices, tp, **cfg_overrides):
    """(step, args): the GPT-350M flagship step as chip_smoke.py builds
    it, over described `devices`, with every argument a shape from
    jax.eval_shape carrying the sharding the step gives it."""
    from apex_tpu.models.gpt import GPT
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    mesh = M.initialize_model_parallel(tensor_model_parallel_size=tp,
                                       devices=list(devices))
    model = GPT(chip_smoke.flagship_config(**cfg_overrides))
    opt = FusedAdam(lr=chip_smoke.LR, master_dtype=jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: init_sharded_optimizer(opt, model, p, mesh), params)

    def placed(sds, spec):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = type(state)(placed(state[0], P()), *(
        placed(buf, P(("pp", "tp"))) for buf in state[1:]))
    tokens = placed(_sds((chip_smoke.BATCH, chip_smoke.SEQ), jnp.int32),
                    P("dp"))
    step = make_tp_dp_train_step(model, opt, mesh, donate=True)
    return step, (state, tokens, tokens)


@pytest.fixture(scope="module")
def flagship_compiled(topo):
    """One compile of the flagship step for the two tests below."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_common, "on_chip", lambda: True)
        step, args = flagship_step(topo.devices[:1], tp=1)
        return step.lower(*args).compile()


def test_flagship_step_compiles_and_fits_one_chip(flagship_compiled):
    """The 350M flagship step (h1024 L24, vocab 50304, bf16, flash
    attention, bf16 Adam state, batch 12 x 1024, no remat, donated
    state) compiles for one v5e with its flash and Adam kernels in it
    and leaves room on the chip — about 1 GiB when this was written.
    Every later PR that grows the step's temporaries meets this first."""
    compiled = flagship_compiled
    # 24 layers x (flash fwd + flash bwd) + the Adam pass
    assert _n_kernels(compiled) >= 49
    m = compiled.memory_analysis()
    # all of the state is donated: only tokens and labels are not
    assert m.argument_size_in_bytes - m.alias_size_in_bytes < 2 ** 20
    assert _bytes(compiled) < HBM_BYTES, (
        f"arguments {m.argument_size_in_bytes / GIB:.2f} GiB + temporaries "
        f"{m.temp_size_in_bytes / GIB:.2f} GiB no longer fit one chip")


def test_flagship_step_is_named_from_inside(flagship_compiled):
    """What a trace of the chip will show: every Mosaic kernel of the
    step under its own name, and every instruction that takes core
    time owned by a scope of the program (monitor.scopes)."""
    import re

    from apex_tpu.monitor import scopes
    from apex_tpu.monitor.comms.hlo import parse_module

    text = flagship_compiled.as_text()
    (entry,) = [c for c in parse_module(text) if c.is_entry]
    kernels = re.findall(
        r'^\s*%(\S+) = .*custom_call_target="tpu_custom_call"', text, re.M)
    by_name = {}
    for name in kernels:
        by_name.setdefault(name.split(".")[0], []).append(name)
    assert {k: len(v) for k, v in by_name.items()} == {
        "flash_fwd": 24, "flash_bwd": 24, "adam_flat": 1}

    found = scopes.owners(text)
    timed = ("fusion", "copy", "custom-call", "all-reduce", "all-gather",
             "reduce-scatter", "convolution", "dot")
    unowned = [i.name for i in entry.instructions
               if i.opcode in timed and found[i.name][0] == scopes.UNOWNED]
    assert not unowned    # the count this test states: none
    # the flat gradient is built by fusions that carry no op_name: the
    # rule hands them the Adam kernel's owner.  The only others of that
    # name join dq, dk and dv behind the flash backward, and say so
    flat = [i.name for i in entry.instructions if i.name.startswith(
        "constant_dynamic-update-slice_fusion")
        and not found[i.name][0].endswith("/attn/flash")]
    assert len(flat) > 200 and {found[n][0] for n in flat} == {
        "optimizer/adam"}
    directions = {d for _, d, _ in found.values()}
    assert directions == {"fwd", "bwd", "step"}


def test_flagship_flash_reads_the_projection_where_it_lies(flagship_compiled):
    """Between the QKV GEMM and the output GEMM a layer has its two
    kernels and nothing else forward; backward, the three fusions that
    write dq, dk and dv into d(projection) in place, one pass together.
    No copy and no transpose of the projection or of the context: the
    (S, B, .) arrays are held batch-major and the kernels address them
    as they lie.  And the benchmark still finds the kernels by what they
    write (benchmarks/lib/hlo.py::kernels_writing)."""
    import math
    import re

    from apex_tpu.monitor import scopes
    from apex_tpu.monitor.comms.hlo import parse_module
    from benchmarks.lib import hlo

    text = flagship_compiled.as_text()
    cfg = chip_smoke.flagship_config()
    layers = cfg.num_layers
    tokens = chip_smoke.BATCH * chip_smoke.SEQ
    context, projection = tokens * cfg.hidden, tokens * 3 * cfg.hidden

    found = scopes.owners(text)
    (entry,) = [c for c in parse_module(text) if c.is_entry]
    mine = [(i, found[i.name][1]) for i in entry.instructions
            if re.match(r"block\d+/attn/flash$", found[i.name][0])]
    kernels = [i.name for i, _ in mine if i.name.startswith("flash_")]
    assert len(kernels) == 2 * layers
    # every layer owns its own two, though all layers share one traced
    # kernel body (ops/flash_attention.py::_qkv_call)
    assert sorted(found[k][0] for k in kernels) == sorted(
        f"block{i}/attn/flash" for i in range(layers) for _ in "fb")
    moved = [i.name for i, _ in mine
             if i.opcode in ("copy", "transpose") and any(
                 math.prod(s.dims) in (context, projection)
                 for s in i.shapes)]
    assert not moved
    fusions = [(i, way) for i, way in mine if i.opcode == "fusion"]
    assert {way for _, way in fusions} == {"bwd"}
    assert len(fusions) <= 3 * layers
    assert all("dynamic-update-slice" in i.name
               and [math.prod(s.dims) for s in i.shapes] == [projection]
               for i, _ in fusions)

    calls = hlo.custom_calls(text)
    by_size = hlo.kernels_writing(calls, context)
    assert sorted(by_size) == sorted(kernels)
    sizes = dict(calls)
    assert all(sizes[k].count(context) == (3 if k.startswith("flash_bwd")
                                           else 1) for k in kernels)


# ---------------- the latent-attention, sparse-expert step ----------------

def _cell_step(devices, config_name, build, batch, seq):
    """(model, step, args): the train step of one of the benchmark's
    expert cells (bf16 Adam state, lr 1e-5, donated state, `batch` x
    `seq` tokens, tensor parallelism 1) over described `devices`, every
    argument a shape.  `build(config)` makes the model from the
    configuration file `benchmarks/configs/<config_name>.json`, once
    the mesh stands."""
    import json

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as M
    from apex_tpu.transformer.training import (
        init_sharded_optimizer,
        make_tp_dp_train_step,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(tensor_model_parallel_size=1,
                                       devices=list(devices))
    model = build(config)
    opt = FusedAdam(lr=1e-5, master_dtype=jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: init_sharded_optimizer(opt, model, p, mesh), params)

    def placed(sds, spec):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = type(state)(placed(state[0], P()), *(
        placed(buf, P(("pp", "tp"))) for buf in state[1:]))
    tokens = placed(_sds((batch, seq), jnp.int32), P("dp"))
    return model, make_tp_dp_train_step(model, opt, mesh, donate=True), (
        state, tokens, tokens)


def _kernel_names(text):
    """The `tpu_custom_call` instructions of a compiled step's text."""
    import re

    return re.findall(
        r'^\s*%(\S+) = .*custom_call_target="tpu_custom_call"', text, re.M)


def _by_base_name(names):
    """{a kernel's name without its `.N`: how many}."""
    count = {}
    for name in names:
        count[name.split(".")[0]] = count.get(name.split(".")[0], 0) + 1
    return count


def test_mla_moe_step_compiles_fits_and_is_named(topo, on_chip):
    """The second model's step at the benchmark's sizes: 680.8M
    parameters held, compiled for one v5e with its flash kernels (keys
    192, values 128), the compiler's grouped-matmul kernels and the Adam
    pass in it, inside the chip's memory, and all but a few shared index
    fusions owned by a scope."""
    from apex_tpu.models.mla_moe import MLAMoE
    from apex_tpu.monitor import scopes
    from apex_tpu.monitor.comms.hlo import parse_module
    from benchmarks.jobs.mla_moe_train import model_config

    # one dense and four expert layers and the MTP module, 16 of 256
    # experts, 16,256 vocabulary rows, 2 x 4096
    _, step, args = _cell_step(
        topo.devices[:1], "joyai-llm-flash",
        lambda config: MLAMoE(model_config(
            config, dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)), 2, 4096)
    # the flat state: 680,834,304 parameters, each leaf tile-aligned
    assert 680_834_304 <= args[0][1].shape[0] < 680_834_304 * 1.001
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    kernels = _kernel_names(text)
    by_name = _by_base_name(kernels)
    # 6 blocks (5 layers and the MTP module's); off the chip the tuner
    # has no v5e entry, so the backward is the two-kernel one; the
    # staging pass runs once for q and once for k a block and direction
    assert {k: v for k, v in by_name.items() if k.startswith(
        ("flash", "adam", "rope"))} == {
        "flash_fwd": 6, "flash_bwd_dq": 6, "flash_bwd_dkv": 6,
        "rope_stage": 12, "rope_unstage": 12, "adam_flat": 1}
    # 5 expert layers x 2 grouped GEMMs x (forward, dgrad, wgrad)
    assert by_name["ragged-dot-none"] == 30
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes - m.alias_size_in_bytes < 2 ** 20
    assert _bytes(compiled) < HBM_BYTES
    # no operand padded to another's width: nothing 256 wide a head
    assert "bf16[64,4096,256]" not in text

    found = scopes.owners(text)
    (entry,) = [c for c in parse_module(text) if c.is_entry]
    timed = ("fusion", "copy", "custom-call", "convolution", "dot", "sort",
             "scatter", "gather")
    unowned = [i.name for i in entry.instructions
               if i.opcode in timed and found[i.name][0] == scopes.UNOWNED]
    assert len(unowned) <= 8, unowned
    # the compiler drops the grouped-matmul kernels' op_name, so each is
    # owned through its users: the expert scope, but for the forward
    # down-projection, whose one user is the weighted scatter-add
    grouped = [found[n][0] for n in kernels
               if n.startswith("ragged-dot-none")]
    assert set(grouped) == {f"block{i}/mlp/{s}" for i in range(1, 6)
                            for s in ("experts", "combine")}
    assert sum(g.endswith("combine") for g in grouped) == 5


# ------------------- the hybrid step and what its checkpoint keeps -------------------

def test_hybrid_step_fits_with_what_its_mixers_keep(topo, on_chip, monkeypatch):
    """The benchmark's `solar-open2-250b` step (4 layers, 8 of 320
    experts, 1 x 4096, bf16 Adam state, `recompute_mixers`) at the
    committed v5e tuner entries: with the activations `hybrid_moe.KEPT`
    names held and the attending layer's checkpoint keeping everything
    it stays inside the chip's memory; the recomputation holds no projection over
    the hidden width and nothing of the attending layer; the chunk-local
    forward still runs twice a KDA layer, not three times."""
    import re

    from apex_tpu.models import hybrid_moe
    from apex_tpu.monitor import scopes
    from apex_tpu.parallel import mesh as M
    from apex_tpu.tune import cache as tune_cache
    from benchmarks.jobs.hybrid_moe_train import model_config

    monkeypatch.setattr(tune_cache, "device_kind", lambda: "v5e")
    _, step, args = _cell_step(
        topo.devices[:1], "solar-open2-250b",
        lambda config: hybrid_moe.HybridMoE(model_config(
            config, dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
            recompute_mixers=True)), 1, 4096)
    hybrid_moe.reset_stats()
    compiled = step.lower(*args).compile()
    M.destroy_model_parallel()

    # what the policy kept: three KDA layers x (q, k, v in front of the convolution and behind
    # it, o, the gated o: 8 x 4096 x 8192 bf16; two rank-128 inner
    # activations; beta's float32 logits)
    assert hybrid_moe.stats() == {
        "kept_bytes": 3 * (8 * 4096 * 8192 * 2 + 2 * 4096 * 128 * 2
                           + 4096 * 64 * 4)}
    assert _bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    by_name = _by_base_name(_kernel_names(text))
    assert {k: by_name.get(k) for k in (
        "kda_locals_fwd", "kda_locals_bwd", "conv_stage", "conv_unstage",
        "flash_fwd")} == {"kda_locals_fwd": 6, "kda_locals_bwd": 3,
                          "conv_stage": 3, "conv_unstage": 3, "flash_fwd": 1}
    found = scopes.owners(text)
    again = {found[name][0] for name in scopes.rematted(text)}
    assert again and all(re.fullmatch(
        r"block[123]/attn/(decay|scan|onorm)", owner) for owner in again)


# ------------------- the short-convolution, top-4 expert step -------------------

def test_shortconv_moe_step_compiles_fits_and_is_named(topo, on_chip,
                                                       monkeypatch):
    """The benchmark's `lfm2-8b-a1b` step (6 layers, 16 of 32 experts,
    32,768 vocabulary rows under a tied head, bf16 Adam state, 1 x
    8192, no recompute, donated state) at the committed v5e tuner
    entry: 954.5M parameters held, compiled for one v5e with the
    single-pass flash kernels at 32 query heads on 8 kv heads of 64,
    the compiler's grouped-matmul kernels and the Adam pass in it,
    inside the chip's memory, and every scope the new readers name
    opened."""
    from apex_tpu.models.shortconv_moe import ShortConvMoE
    from apex_tpu.monitor import scopes
    from apex_tpu.parallel import mesh as M
    from apex_tpu.tune import cache as tune_cache
    from benchmarks.jobs.shortconv_moe_train import model_config

    monkeypatch.setattr(tune_cache, "device_kind", lambda: "v5e")
    model, step, args = _cell_step(
        topo.devices[:1], "lfm2-8b-a1b",
        lambda config: ShortConvMoE(model_config(
            config, dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)), 1, 8192)
    # one leaf for embedding and head
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 954_523_904
    assert 954_523_904 <= args[0][1].shape[0] < 954_523_904 * 1.001
    compiled = step.lower(*args).compile()
    M.destroy_model_parallel()

    text = compiled.as_text()
    by_name = _by_base_name(_kernel_names(text))
    assert {k: v for k, v in by_name.items()
            if k.startswith(("flash", "adam"))} == {
        "flash_fwd": 1, "flash_bwd": 1, "adam_flat": 1}
    # 4 expert layers x 2 grouped GEMMs x (forward, dgrad, wgrad)
    assert by_name["ragged-dot-none"] == 24
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes - m.alias_size_in_bytes < 2 ** 20
    # the compiler's count: 5.33 GiB of arguments + 9.54 of temporaries
    assert 14.0 * GIB < _bytes(compiled) < 15.3 * GIB
    found = scopes.owners(text)
    owners = {owner for owner, _, _ in found.values()}
    assert {f"block{i}/attn/{s}" for i in (0, 1, 3, 4, 5)
            for s in ("in_proj", "shortconv", "out_proj")} | {
        f"block2/attn/{s}" for s in ("qkv", "qknorm_rope", "flash",
                                     "proj")} | {
        f"block{i}/mlp/{s}" for i in (2, 3, 4, 5)
        for s in ("router", "dispatch", "experts", "combine")} | {
        "block0/mlp/gate_up", "block1/mlp/down", "embed", "head",
        "loss"} <= owners
    # the grouped GEMMs: under the experts' scope, but for the forward
    # down-projection, whose one user is the weighted scatter-add
    grouped = [found[n][0] for n in found if n.startswith("ragged-dot-none")]
    assert sum(g.endswith("mlp/combine") for g in grouped) == 4
    assert sum(g.endswith("mlp/experts") for g in grouped) == 20
