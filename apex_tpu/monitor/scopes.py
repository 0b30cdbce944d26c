"""Who owns a step's device time: the names the program gives itself.

The model, the step builder and the optimizer open a `jax.named_scope`
per sublayer and phase, and every Pallas kernel passes a `name=`.  Both
are metadata at trace time: they end up in the compiled program's text
(`op_name="jit(local_step)/jvp(block3)/attn/qkv/dot_general"`, the
instruction `%flash_fwd.7`) and cost nothing per step.  A profiler
trace names device events by instruction, so `owners` over that text
says whose time each event is.

A fusion carries the `op_name` of one of its instructions, and the
compiler's own copies, bitcasts and flat-buffer updates carry none:
those take their users' owner, or else their producers' (`owners`).
"""

from __future__ import annotations

import re

from apex_tpu.monitor.comms.hlo import parse_module
from apex_tpu.monitor.compile import startup

_SUBLAYERS = ("ln1", "attn", "attn/qkv", "attn/flash", "attn/proj",
              "ln2", "mlp", "mlp/fc1", "mlp/gelu", "mlp/fc2",
              # the latent-attention block (models/mla_moe.py): its five
              # projections and the rotary embedding ...
              "attn/q_a", "attn/q_b", "attn/kv_a", "attn/kv_b", "attn/rope",
              # ... its dense SwiGLU, and the held-experts layer
              "mlp/gate_up", "mlp/down", "mlp/router", "mlp/dispatch",
              "mlp/experts", "mlp/shared", "mlp/combine",
              # the hybrid block (models/hybrid_moe.py): the attention's
              # output gate; the delta-rule mixer's convolution (with
              # SiLU and the unit scaling of q and k), its decay pair
              # and beta, the chunked scan (ops/delta_rule.py), and its
              # output norm and gate
              "attn/gate", "attn/conv", "attn/decay", "attn/scan",
              "attn/onorm",
              # its latent attention without positions: the uncompressed
              # query projection and the head-major staging of q and k
              # with nothing turned (`attn/rope` stays the rotating
              # block's); and what a step of packed rows spends on
              # knowing its documents, filed under block0
              "attn/q", "attn/stage", "attn/segments",
              # the short-convolution block (models/shortconv_moe.py):
              # the mixer's two projections and the gates and taps
              # between them (`attn/conv` stays the delta-rule mixer's
              # staging convolution); and, on its layers that attend,
              # q's and k's norm and rotation on the way to the kernels
              "attn/in_proj", "attn/shortconv", "attn/out_proj",
              "attn/qknorm_rope",
              # the post-normed hybrid (models/olmo_hybrid.py): its full
              # attention's norm of q and k over the whole projection,
              # nothing turned
              "attn/qknorm")
# every scope path the program may open; `block{i}` is a layer by index
# (`_tap` spells it the same way), `block` a layer of a scanned stack
OWNERS = (
    "unflatten", "dp_reduce", "pp_sync",
    "optimizer", "optimizer/flatten_grads", "optimizer/adam",
    "embed", "final_ln", "head", "loss",
    # the multi-token-prediction module around its block (a `block{i}`
    # of its own): input norms and projection, final norm, head and loss
    "mtp", "mtp/proj", "mtp/head",
    *(f"block{{i}}/{s}" for s in _SUBLAYERS),
    *(f"block/{s}" for s in _SUBLAYERS))
# the `name=` of every pl.pallas_call in apex_tpu/ops: an unnamed call
# would take its enclosing scope's name and pass for that scope
KERNELS = (
    "flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv",
    "flash_decode", "adam_flat", "adam_flat_seg", "sgd_flat",
    "adagrad_flat", "lamb_phase1", "lamb_phase1_seg", "lamb_phase2",
    "lamb_phase2_seg", "per_tensor_sumsq", "xent_fwd", "xent_bwd",
    "ln_fwd", "ln_bwd", "softmax_fwd", "softmax_bwd", "fused_dense",
    "welford", "rope_stage", "rope_unstage", "kda_locals_fwd",
    "kda_locals_bwd", "conv_stage", "conv_unstage", "gmm", "tgmm")
UNOWNED = "unowned"

# jvp( transpose( vmap( ... and every ")": a transform wraps the first
# scope inside it.  "jit(f" stays whole, so that a jitted function that
# happens to be called `loss` is not taken for the scope
_WRAPPER = re.compile(r"\b(?!jit\()\w+\(|\)")
_VOCABULARY = "|".join(          # longest first: the first match is the longest
    re.escape(p).replace(re.escape("{i}"), r"\d+")
    for p in sorted(OWNERS, key=len, reverse=True))
_PATH = re.compile(f"(?:^|/)({_VOCABULARY})(?=/|$)")
_WHOLE_PATH = re.compile(_VOCABULARY)
# `jax.checkpoint` puts these two segments between the scope it was
# called in and the scopes opened inside it, and spells the former once
# more for the transform around it:
# "transpose(jvp(block3))/attn/jvp(block3)/attn/checkpoint/
# rematted_computation/scan/dot_general"
_REMAT = re.compile(r"/(?:checkpoint|rematted_computation)(?=/|$)")


def owner_of(op_name: str):
    """(owner, direction) an `op_name` states itself: the longest
    vocabulary path in it, or None; "bwd" under `transpose(jvp(`, "fwd"
    under `jvp(`, else "step".  Where a path is followed by a longer
    spelling of itself (what `jax.checkpoint` leaves: the enclosing
    scopes twice, then the scopes inside), the longer one is the
    owner; a recomputed forward runs in the backward and is "bwd"."""
    direction = ("bwd" if "transpose(jvp(" in op_name
                 else "fwd" if "jvp(" in op_name else "step")
    owner = None
    for m in _PATH.finditer(_REMAT.sub("", _WRAPPER.sub("", op_name))):
        if owner is None or m.group(1).startswith(owner):
            owner = m.group(1)
    return owner, direction


def rematted(hlo_text: str) -> frozenset:
    """The instructions of a compiled program's text whose own
    `op_name` stands under `rematted_computation`: a forward that
    `jax.checkpoint` runs again in the backward.  A lower bound on
    what is recomputed: an instruction that states no `op_name` is not
    in it, and a fusion states the `op_name` of one of its
    instructions."""
    return frozenset(
        i.name for comp in parse_module(hlo_text)
        for i in comp.instructions
        if "/rematted_computation/" in i.op_name + "/")


def _shared(found):
    """What (owner, direction) pairs agree on: the longest vocabulary
    path all the owners start with and the first pair's direction (an
    instruction runs for its first user), or None."""
    found = [f for f in found if f is not None]
    if not found:
        return None
    paths = [owner.split("/") for owner, _ in found]
    n = 0
    while all(len(p) > n and p[n] == paths[0][n] for p in paths):
        n += 1
    while n and not _WHOLE_PATH.fullmatch("/".join(paths[0][:n])):
        n -= 1
    return ("/".join(paths[0][:n]), found[0][1]) if n else None


def _first_of_one_sublayer(found):
    """The first (owner, direction) pair where all are the same
    sublayer of different layers (`block1/attn/scan`,
    `block3/attn/scan`), else None."""
    found = [f for f in found if f is not None]
    paths = [owner.split("/") for owner, _ in found]
    if found and len({tuple(p[1:]) for p in paths}) == 1 and all(
            re.fullmatch(r"block\d+", p[0]) for p in paths):
        return found[0]
    return None


def owners(hlo_text: str) -> dict:
    """{instruction: (owner, direction, opcode)} for a compiled
    program's text (`compiled.as_text()`).

    An instruction's owner is the longest vocabulary path in its own
    `op_name`; failing that, what its users share (followed through
    users that state none), failing that, what its operands' producers
    share; failing that, for a fusion that reads nothing a scope made
    (a buffer of zeros, or of a constant, that the compiler fills once
    for every layer's scan) and whose users are one sublayer of several
    layers, the first of them: a reader sums a sublayer over the
    layers; failing that, inside a loop's body or condition, the owner
    of the `while` instruction that runs it (a loop the compiler made
    of one instruction, a gather say, names the loop and nothing
    inside); else `UNOWNED`.  The direction comes with the owner."""
    out = {}
    inherited = {}      # computation -> what the loop running it states
    # printed order puts a computation before the ones that call it:
    # backwards, a loop is resolved before its body
    for comp in reversed(parse_module(hlo_text)):
        instrs = comp.instructions
        own = {i.name: owner_of(i.op_name) for i in instrs}
        found = {n: (o if o[0] else None) for n, o in own.items()}
        users = {i.name: [] for i in instrs}
        for i in instrs:
            for operand in i.operand_names:
                if operand in users:
                    users[operand].append(i.name)
        # printed order is a topological one: users are resolved before
        # what they use going backwards, producers going forwards
        for i in reversed(instrs):
            if found[i.name] is None:
                found[i.name] = _shared(found[u] for u in users[i.name])
        for i in instrs:
            if found[i.name] is None:
                found[i.name] = _shared(
                    found.get(p) for p in i.operand_names)
        for i in instrs:
            if (found[i.name] is None and i.opcode == "fusion"
                    and not any(found.get(p) for p in i.operand_names)):
                found[i.name] = _first_of_one_sublayer(
                    found[u] for u in users[i.name])
        for i in instrs:
            if found[i.name] is None:
                found[i.name] = inherited.get(comp.name)
            if i.opcode == "while" and found[i.name] is not None:
                for called in i.called:
                    inherited.setdefault(called, found[i.name])
            owner, direction = found[i.name] or (UNOWNED, own[i.name][1])
            out[i.name] = (owner, direction, i.opcode)
    return out


_programs = {}     # the steps this process built: name -> (jitted, shapes)


def register(name: str, jitted, args) -> bool:
    """Called by a step builder when it builds a program: keeps the
    jitted callable and the shapes, dtypes and shardings of `args`
    under `name`.  One entry a name; held strongly, because the reader
    runs after the job's own reference to the step is gone.  False,
    and nothing kept, where `args` are tracers: that build runs under
    another transformation, not on a device."""
    import jax

    startup.arm()
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
        return False

    def abstract(a):
        # an uncommitted array's placement is JAX's choice, not the step's
        placed = getattr(a, "committed", True)
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=getattr(a, "sharding", None) if placed else None)

    _programs[name] = (jitted, jax.tree.map(abstract, args))
    return True


def registered() -> tuple:
    """The names of the programs this process registered."""
    return tuple(_programs)


def step_text(name: str = "local_step") -> str:
    """The compiled text of the registered program `name`.  After the
    program has run this lowers and compiles nothing anew: tracing,
    lowering and the executable come from JAX's in-process caches."""
    jitted, args = _programs[name]
    with startup.span("scopes.step_text"):
        return jitted.lower(*args).compile().as_text()


def step_owners(name: str = "local_step") -> dict:
    """`owners` of the registered program `name`."""
    return owners(step_text(name))


def step_rematted(name: str = "local_step") -> frozenset:
    """`rematted` of the registered program `name`."""
    return rematted(step_text(name))


def _jaxprs_in(eqn):
    """The jaxprs an equation carries in its parameters: the body of a
    `pjit`, `custom_vjp_call`, `checkpoint`, `scan`, `while`,
    `shard_map`, the branches of a `cond`."""
    from jax.extend import core

    for value in eqn.params.values():
        for inner in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(inner, "jaxpr", inner)     # a ClosedJaxpr
            if isinstance(inner, core.Jaxpr):
                yield inner


def count_eqns(jaxpr) -> int:
    """The equations of a jaxpr and of every jaxpr nested in it."""
    return sum(1 + sum(count_eqns(inner) for inner in _jaxprs_in(eqn))
               for eqn in jaxpr.eqns)


def kernels_in(jaxpr, found=None) -> dict:
    """{kernel: {"call_sites": n, "body_eqns": m}} for the Pallas calls
    of a jaxpr, every nested body walked: `n` the `pallas_call`
    equations that name the kernel, `m` the equations of their bodies
    together (`count_eqns`, so a body's own branches and loops count).
    A call site is what Mosaic lowers: one in a scanned body is one,
    however often it runs."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            cell = found.setdefault(eqn.params.get("name") or "pallas_call",
                                    {"call_sites": 0, "body_eqns": 0})
            cell["call_sites"] += 1
            cell["body_eqns"] += count_eqns(eqn.params["jaxpr"])
        else:
            for inner in _jaxprs_in(eqn):
                kernels_in(inner, found)
    return found


def step_kernels(name: str = "local_step") -> dict:
    """`kernels_in` the traced jaxpr of the registered program `name`:
    the static twin of the ledger's kernel spans.  After the program
    has run, the jaxpr comes from JAX's cache (as `step_text`'s does)."""
    jitted, args = _programs[name]
    return kernels_in(jitted.trace(*args).jaxpr.jaxpr)
