"""monitor.scopes: the vocabulary of owners, the ownership rule over a
compiled program's text, and the step a process ran.

The rule is held to a recorded text: `data/step_v5e_excerpt.hlo.txt` is
cut from the GPT-350M step compiled for the described v5e (real
instructions of its entry computation, in their order; layouts,
backend_config and frontend attributes taken out)."""

import ast
import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.monitor import scopes
from apex_tpu.monitor.comms.hlo import parse_module
from apex_tpu.optimizers.fused_adam import FusedAdam
from apex_tpu.parallel import mesh as M
from apex_tpu.transformer.training import (
    init_sharded_optimizer,
    make_tp_dp_train_step,
)

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.join(os.path.dirname(HERE), "apex_tpu")
FLASH0 = "block0/attn/flash"


# ------------------------------ the rule ------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(local_step)/jvp(block3)/attn/qkv/dot_general",
     ("block3/attn/qkv", "fwd")),
    ("jit(local_step)/transpose(jvp(block11))/mlp/fc2/dot_general",
     ("block11/mlp/fc2", "bwd")),
    ("jit(local_step)/jvp(block0)/attn/flash/flash_fwd/pallas_call",
     (FLASH0, "fwd")),
    ("jit(step)/jvp(while)/body/block/ln1/reduce_sum", ("block/ln1", "fwd")),
    ("jit(local_step)/optimizer/adam/adam_flat/pallas_call",
     ("optimizer/adam", "step")),
    ("jit(local_step)/optimizer/convert_element_type", ("optimizer", "step")),
    ("jit(local_step)/jvp(block2)/mlp/gelu/jit(gelu)/tanh",
     ("block2/mlp/gelu", "fwd")),
    # under `jax.checkpoint` the enclosing scopes come twice and the
    # scopes inside follow two segments of its own: the longer spelling
    # owns, and the recomputed forward is the backward's
    ("jit(local_step)/transpose(jvp(block3))/attn/jvp(block3)/attn/"
     "checkpoint/rematted_computation/scan/dot_general",
     ("block3/attn/scan", "bwd")),
    ("jit(local_step)/transpose(jvp(block0))/attn/jvp(block0)/attn/"
     "checkpoint/gate/transpose(jvp())/mul", ("block0/attn/gate", "bwd")),
    ("jit(local_step)/jvp(block1)/attn/scan/checkpoint/jit(_where)/select_n",
     ("block1/attn/scan", "fwd")),
    # a later path that does not spell the first one on is not its owner
    ("jit(local_step)/jvp(mtp)/proj/embed/gather", ("mtp/proj", "fwd")),
    # direction without an owner; a jitted function is not a scope; a
    # block alone, or a sublayer outside a block, is no vocabulary path
    ("jit(local_step)/jvp()/dot_general", (None, "fwd")),
    ("jit(loss)/reduce_sum", (None, "step")),
    ("jit(f)/block7/add", (None, "step")),
    ("jit(f)/attn/qkv/dot_general", (None, "step")),
    ("", (None, "step")),
])
def test_owner_of_an_op_name(op_name, want):
    assert scopes.owner_of(op_name) == want


@pytest.fixture(scope="module")
def excerpt():
    with open(os.path.join(HERE, "data", "step_v5e_excerpt.hlo.txt")) as f:
        return scopes.owners(f.read())


@pytest.mark.parametrize("instruction,want,why", [
    ("flash_fwd.24", (FLASH0, "fwd", "custom-call"), "its own op_name"),
    ("flash_bwd.47", (FLASH0, "bwd", "custom-call"),
     "its own op_name, under transpose(jvp("),
    ("fusion.2006", ("block0/mlp/fc1", "bwd", "fusion"),
     "a fusion carries one of its instructions' op_name"),
    ("adam_flat.1", ("optimizer/adam", "step", "custom-call"),
     "outside the differentiated function the direction is step"),
    ("bitcast.1938", (FLASH0, "fwd", "bitcast"),
     "no op_name: its one user, the kernel"),
    ("copy-start.1033", (FLASH0, "bwd", "copy-start"),
     "through copy-done.1033, which has no op_name either, to the "
     "backward kernel; the direction comes with the owner"),
    ("constant_dynamic-update-slice_fusion.262",
     ("optimizer/adam", "step", "fusion"),
     "the flat gradient: three fusions without op_name down to "
     "fusion.9, whose own op_name says optimizer/adam"),
    ("copy-done.986", (FLASH0, "fwd", "copy-done"),
     "its users lead to kernels of block0 and block1, which share no "
     "path; its producer chain starts at broadcast.31, owned through "
     "flash_fwd.24"),
    ("constant.88", (scopes.UNOWNED, "step", "constant"),
     "kernels of two blocks use it and nothing produces it"),
    ("tuple.640", (scopes.UNOWNED, "step", "tuple"),
     "the root: no user, and its operands are the optimizer's and "
     "the loss's"),
])
def test_ownership_rule_on_recorded_text(excerpt, instruction, want, why):
    assert excerpt[instruction] == want, why


REMATTED = ("jit(local_step)/transpose(jvp(block3))/attn/jvp(block3)/attn/"
            "checkpoint/rematted_computation/scan/dot_general")


@pytest.mark.parametrize("op_name,again", [
    # the docstring's own example: owned and directed as before, and in
    # the set
    (REMATTED, True),
    # the checkpoint's first forward, and a backward that is no
    # recomputation
    ("jit(local_step)/jvp(block1)/attn/scan/checkpoint/jit(_where)/select_n",
     False),
    ("jit(local_step)/transpose(jvp(block0))/attn/jvp(block0)/attn/"
     "checkpoint/gate/transpose(jvp())/mul", False),
    # the segment is a whole one
    ("jit(f)/transpose(jvp(block1))/attn/rematted_computation_of_mine/neg",
     False),
    ("", False),
])
def test_rematted_is_what_stands_under_the_recomputation(op_name, again):
    """`rematted` reads an instruction's own `op_name` and nothing
    else: what states none (`%copy`) is not in the set, whoever uses
    it."""
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    text = f"""HloModule m

ENTRY %main () -> () {{
  %p = f32[8] parameter(0)
  %copy = f32[8] copy(%p)
  %a = f32[8] negate(%copy){meta}
  %b = f32[8] negate(%copy), metadata={{op_name="{REMATTED}"}}
}}
"""
    assert scopes.rematted(text) == frozenset({"a", "b"} if again else {"b"})
    if op_name == REMATTED:
        assert scopes.owner_of(op_name) == ("block3/attn/scan", "bwd")
        assert scopes.owners(text)["a"] == ("block3/attn/scan", "bwd",
                                            "negate")


def test_users_that_disagree_share_their_longest_path():
    """Not recorded (the flagship step has no such instruction): a
    copy used by the QKV GEMM and by the flash kernel of one block
    belongs to that block's attention; used by two sublayers of the
    block, to nothing the vocabulary names, so its producer decides."""
    text = """HloModule m

ENTRY %main () -> () {
  %p = f32[8] parameter(0), metadata={op_name="jit(f)/jvp(block1)/ln1/mul"}
  %copy.1 = f32[8] copy(%p)
  %copy.2 = f32[8] copy(%p)
  %a = f32[8] negate(%copy.1), metadata={op_name="jit(f)/jvp(block1)/attn/qkv/neg"}
  %b = f32[8] negate(%copy.1), metadata={op_name="jit(f)/jvp(block1)/attn/flash/neg"}
  %c = f32[8] negate(%copy.2), metadata={op_name="jit(f)/jvp(block1)/attn/proj/neg"}
  %d = f32[8] negate(%copy.2), metadata={op_name="jit(f)/transpose(jvp(block1))/mlp/fc1/neg"}
}
"""
    found = scopes.owners(text)
    assert found["copy.1"] == ("block1/attn", "fwd", "copy")
    assert found["copy.2"] == ("block1/ln1", "fwd", "copy")


def test_a_buffer_filled_once_for_every_layers_scan_goes_to_the_first():
    """Not recorded: the compiler fills one buffer of zeros for the
    scans of three layers.  A fusion that reads nothing a scope made
    and whose users are one sublayer of several layers is the first
    one's; one that reads a scope's array, or with users of two
    sublayers, stays as the other rules leave it."""
    text = """HloModule m

ENTRY %main () -> () {
  %zeros = f32[8] fusion(), kind=kLoop, calls=%fused
  %a = f32[8] negate(%zeros), metadata={op_name="jit(f)/jvp(block1)/attn/scan/neg"}
  %b = f32[8] negate(%zeros), metadata={op_name="jit(f)/transpose(jvp(block3))/attn/scan/neg"}
  %mixed = f32[8] fusion(), kind=kLoop, calls=%fused.1
  %c = f32[8] negate(%mixed), metadata={op_name="jit(f)/jvp(block1)/attn/scan/neg"}
  %d = f32[8] negate(%mixed), metadata={op_name="jit(f)/jvp(block2)/attn/conv/neg"}
  %one = f32[] constant(1)
  %ones = f32[8] fusion(%one), kind=kLoop, calls=%fused.3
  %g = f32[8] negate(%ones), metadata={op_name="jit(f)/jvp(block2)/mlp/dispatch/neg"}
  %h = f32[8] negate(%ones), metadata={op_name="jit(f)/jvp(block3)/mlp/dispatch/neg"}
  %p = f32[8] parameter(0), metadata={op_name="jit(f)/jvp(block1)/ln2/mul"}
  %q = f32[8] parameter(1), metadata={op_name="jit(f)/jvp(block2)/ln2/mul"}
  %fed = f32[8] fusion(%p, %q), kind=kLoop, calls=%fused.2
  %e = f32[8] negate(%fed), metadata={op_name="jit(f)/jvp(block1)/mlp/router/neg"}
  %f = f32[8] negate(%fed), metadata={op_name="jit(f)/jvp(block2)/mlp/router/neg"}
}
"""
    found = scopes.owners(text)
    assert found["zeros"] == ("block1/attn/scan", "fwd", "fusion")
    assert found["ones"] == ("block2/mlp/dispatch", "fwd", "fusion")
    assert found["mixed"][0] == scopes.UNOWNED
    assert found["fed"][0] == scopes.UNOWNED


def test_a_loop_the_compiler_made_belongs_to_the_instruction_it_was():
    """Not recorded either: the TPU compiler turns one gather of the
    delta rule's triangular inverse into a loop, names the `while` and
    nothing in its body; a body's instructions take the loop's owner,
    nested loops down, where no rule inside the body finds one."""
    text = """HloModule m

%inner_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8] get-tuple-element(%p), index=1
  %fusion.7 = f32[8] fusion(%x), kind=kLoop, calls=%fused
  ROOT %t = (s32[], f32[8]) tuple(%i, %fusion.7)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

%body (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[], f32[8]) parameter(0)
  %z = f32[8] constant(0)
  %named = f32[8] negate(%z), metadata={op_name="jit(f)/jvp(block2)/attn/conv/neg"}
  %y = f32[8] get-tuple-element(%q), index=1
  %copy.9 = f32[8] copy(%y)
  %t.2 = (s32[], f32[8]) tuple(%j, %copy.9)
  %j = s32[] get-tuple-element(%q), index=0
  ROOT %while.2 = (s32[], f32[8]) while(%t.2), condition=%cond, body=%inner_body
}

ENTRY %main () -> () {
  %init = (s32[], f32[8]) tuple()
  %while.1 = (s32[], f32[8]) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/jvp(block1)/attn/scan/jit(diagonal)/gather"}
  %init.2 = (s32[], f32[8]) tuple()
  %other = (s32[], f32[8]) while(%init.2), condition=%cond, body=%body
}
"""
    found = scopes.owners(text)
    scan = ("block1/attn/scan", "fwd")
    assert found["while.1"] == (*scan, "while")
    assert found["copy.9"] == (*scan, "copy")
    assert found["while.2"] == (*scan, "while")          # and on down
    assert found["fusion.7"] == (*scan, "fusion")
    assert found["lt"] == (*scan, "compare")             # the condition too
    # what a body states itself stays, and a loop that states nothing
    # hands nothing down
    assert found["named"] == ("block2/attn/conv", "fwd", "negate")
    assert found["other"][0] == scopes.UNOWNED


# --------------------------- the vocabulary ---------------------------

def _calls(tree, *dotted):
    """Call nodes of `tree` whose callee is spelled `a.b`."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and (node.func.value.id, node.func.attr) == dotted):
            yield node


def _spelled(node):
    """A string argument as the source spells it, an f-string's fields
    as `{name}`; None if it is not a string literal."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        return "".join(
            part.value if isinstance(part, ast.Constant)
            else "{" + ast.unparse(part.value) + "}" for part in node.values)
    return None


def _sources():
    for path in sorted(glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            yield os.path.relpath(path, PACKAGE), ast.parse(f.read())


def test_every_scope_in_the_program_is_in_the_vocabulary():
    """A `jax.named_scope` the vocabulary does not know would open a
    path that `owners` cannot read: its time would fall to `unowned` or
    to an enclosing scope.  A scope may spell a whole path or a run of
    its segments (`block{i}`, `attn`, `qkv` nest into one path)."""
    runs = set()
    for path in scopes.OWNERS:
        parts = path.split("/")
        runs |= {"/".join(parts[a:b]) for a in range(len(parts))
                 for b in range(a + 1, len(parts) + 1)}
    used = {}
    for where, tree in _sources():
        for call in _calls(tree, "jax", "named_scope"):
            used.setdefault(_spelled(call.args[0]), where)
    assert len(used) >= 20    # the walk finds them at all
    strangers = {name: where for name, where in used.items()
                 if name not in runs}
    assert not strangers
    # and no vocabulary path is one the program never opens
    unopened = [p for p in scopes.OWNERS
                if not set(p.split("/")) <= {s for u in used
                                             for s in u.split("/")}]
    assert not unopened


def test_every_pallas_call_has_a_name_from_the_vocabulary():
    """An unnamed Pallas call is named after the scope around it
    (`%mlp.1`) and would pass for that scope."""
    names = []
    for where, tree in _sources():
        for call in _calls(tree, "pl", "pallas_call"):
            given = {k.arg: k.value for k in call.keywords}
            assert "name" in given, f"{where}:{call.lineno} has no name="
            name = _spelled(given["name"])
            assert name in scopes.KERNELS, f"{where}:{call.lineno}: {name}"
            names.append(name)
    assert len(names) >= 23
    assert set(names) == set(scopes.KERNELS)


# ------------------------- the step that ran -------------------------

def _tiny_step(tp, sequence_parallel):
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=jax.devices()[:2 * tp])
    model = GPT(GPTConfig(vocab_size=64, seq_len=16, hidden=32, num_layers=2,
                          num_heads=4, dropout=0.0,
                          sequence_parallel=sequence_parallel))
    params = model.init(jax.random.PRNGKey(8))
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    return step, state, tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def compiles():
    """Every backend compile of this process, from here on (JAX has no
    way to take a listener off again: one for the module)."""
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, **kw: seen.append(event)
        if event.endswith("backend_compile_duration") else None)
    return seen


@pytest.mark.parametrize("tp,sequence_parallel", [(1, False), (2, True)])
def test_step_owners_names_every_gemm_of_the_step_that_ran(
        tp, sequence_parallel, compiles):
    step, state, tokens, labels = _tiny_step(tp, sequence_parallel)
    state, loss = step(state, tokens, labels)
    assert np.isfinite(float(loss))

    compiled_before = len(compiles)
    text = scopes.step_text()
    found = scopes.owners(text)
    # the executable the step ran, not a second one
    assert len(compiles) == compiled_before > 0

    gemms = [i for comp in parse_module(text) for i in comp.instructions
             if i.op_name.endswith("dot_general")]
    assert len(gemms) >= 2 * 3 * 4 + 3    # 2 layers x (fwd + 2 bwd) x 4
    for i in gemms:
        owner, direction, _ = found[i.name]
        assert owner.startswith(("block0/", "block1/", "head", "embed")), (
            i.name, i.op_name, owner)
        assert direction in ("fwd", "bwd")
    owners = {owner for owner, _, _ in found.values()}
    assert {"unflatten", "dp_reduce", "optimizer/adam",
            "optimizer/flatten_grads", "embed", "final_ln", "head", "loss",
            "block1/ln2", "block0/mlp/gelu"} <= owners
    # the TP/SP collectives inherit the sublayer that calls them
    kinds = ("all-reduce", "all-gather", "reduce-scatter")
    collectives = [v for v in found.values() if v[2] in kinds]
    assert collectives and all(v[0] != scopes.UNOWNED for v in collectives)
    if sequence_parallel:
        assert any(owner == "block0/attn/qkv" and opcode == "all-gather"
                   for owner, _, opcode in found.values())


def test_step_owners_names_the_latent_attention_block():
    """The vocabulary over the second model: a step of `MLAMoE` (one
    dense layer, one expert layer, the MTP module) through the same
    builder, every GEMM and every grouped GEMM owned by the scope that
    asked for it, and every new path of the vocabulary opened."""
    from apex_tpu.models.mla_moe import MLAMoE, MLAMoEConfig

    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = MLAMoE(MLAMoEConfig(
        vocab_size=64, hidden=32, num_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=8,
        n_routed_experts=16, num_experts_per_tok=3, num_expert_layers=1,
        experts_first=4, experts_count=8, rope_theta=1e4))
    params = model.init(jax.random.PRNGKey(8))
    opt = FusedAdam(lr=3e-3, use_pallas=False)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    state, loss = step(state, tokens, jnp.roll(tokens, -1, axis=1))
    assert np.isfinite(float(loss))

    text = scopes.step_text()
    found = scopes.owners(text)
    gemms = [i for comp in parse_module(text) for i in comp.instructions
             if i.op_name.endswith("dot_general")]
    assert len(gemms) >= 3 * (3 * 5 + 2 + 4 + 2 + 1)
    for i in gemms:
        owner, direction, _ = found[i.name]
        assert owner.startswith(("block0/", "block1/", "block2/", "head",
                                 "mtp/")), (i.name, i.op_name, owner)
        assert direction in ("fwd", "bwd")
    owners = {owner for owner, _, _ in found.values()}
    new = {f"block2/{s}" for s in (
        "attn/q_a", "attn/q_b", "attn/kv_a", "attn/kv_b", "attn/rope",
        "attn/flash", "attn/proj", "mlp/router", "mlp/dispatch",
        "mlp/experts", "mlp/shared", "mlp/combine")}
    assert new | {"block0/mlp/gate_up", "block0/mlp/down", "mtp/proj",
                  "mtp/head", "embed", "final_ln", "head", "loss",
                  "optimizer/adam", "unflatten"} <= owners
    # the grouped GEMMs of both expert layers: gate-and-up and down,
    # each forward, input gradient and weight gradient (off the chip a
    # ragged_dot is so many masked dot_generals under the same name)
    for block in ("block1", "block2"):
        grouped = [found[i.name][1] for i in gemms
                   if found[i.name][0] == f"{block}/mlp/experts"]
        assert grouped.count("fwd") >= 2 and grouped.count("bwd") >= 4
    # every path the vocabulary added is one this step opens
    added = {p.replace("block{i}/", "") for p in scopes.OWNERS
             if p.startswith("block{i}/")} - {
        "ln1", "attn", "attn/qkv", "attn/flash", "attn/proj", "ln2", "mlp",
        "mlp/fc1", "mlp/gelu", "mlp/fc2"} - {
        # the hybrid block's, which its own step opens
        # (test_hybrid_moe.py::test_every_instruction_of_the_step_is_owned)
        "attn/gate", "attn/conv", "attn/decay", "attn/scan", "attn/onorm",
        # and those of its latent kind over packed documents
        # (test_kimi_linear.py::test_every_instruction_of_the_step_is_owned)
        "attn/q", "attn/stage", "attn/segments",
        # the short-convolution block's, which its own step opens
        # (test_shortconv_moe.py::test_every_instruction_of_the_step_is_owned)
        "attn/in_proj", "attn/shortconv", "attn/out_proj",
        "attn/qknorm_rope",
        # the post-normed hybrid's full attention, which its own step
        # opens (test_olmo_hybrid.py::test_a_step_through_the_step_builder)
        "attn/qknorm"}
    assert added == {s.split("/", 1)[1] for s in new} - {
        "attn/flash", "attn/proj"} | {"mlp/gate_up", "mlp/down"}


def test_scopes_change_no_arithmetic(monkeypatch):
    """The same step traced with `jax.named_scope` a no-op gives the
    same loss and the same new state, bit for bit."""
    def two_steps():
        step, state, tokens, labels = _tiny_step(2, True)
        state, _ = step(state, tokens, labels)
        state, loss = step(state, tokens, labels)
        return jax.tree.map(np.asarray, (loss, state))

    with_scopes = two_steps()
    assert "block0/ln1" in {v[0] for v in scopes.step_owners().values()}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = two_steps()
    assert {v[0] for v in scopes.step_owners().values()} == {scopes.UNOWNED}
    jax.tree.map(np.testing.assert_array_equal, with_scopes, without)


def test_a_scanned_stack_is_owned_as_block():
    """GPTPipelined scans one block body over its layers: the scope is
    `block`, without an index, opened inside the scanned body."""
    from apex_tpu.models.gpt import GPTPipelined

    model = GPTPipelined(
        GPTConfig(vocab_size=64, seq_len=16, hidden=32, num_layers=2,
                  num_heads=4, dropout=0.0),
        num_microbatches=2, pipeline_parallel_size=1)
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # (pp, chunks, layers, ...) -> this stage's (layers, ...)
    stage = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype),
        params["blocks"])
    x = jax.ShapeDtypeStruct((16, 2, 32), jnp.float32)
    grad = jax.grad(lambda p, x: model._stage_fn(p, x, 0).sum())
    text = jax.jit(shard_map(
        grad, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False)).lower(stage, x).compile().as_text()
    owners = {owner for owner, _, _ in scopes.owners(text).values()}
    assert {"block/ln1", "block/attn/qkv", "block/attn/flash",
            "block/mlp/fc2"} <= owners
