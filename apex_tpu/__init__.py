"""apex_tpu — a TPU-native training-systems toolkit.

A ground-up JAX/XLA/Pallas re-design of the capability surface of NVIDIA
Apex (reference: apex/__init__.py): mixed precision, fused kernels
(normalization, softmax, attention, losses, optimizers), data-parallel
training utilities, and a Megatron-style tensor/pipeline/sequence
parallelism library — all expressed as functional transforms over a
`jax.sharding.Mesh` instead of CUDA streams + NCCL process groups.

Subpackages (lazily importable):
  amp          — precision policies + dynamic loss scaling (≡ apex.amp)
  ops          — Pallas/XLA fused kernels (≡ csrc/ + apex.normalization,
                 apex.mlp, apex.fused_dense, apex.contrib kernels)
  optimizers   — fused optimizers over flat buffers (≡ apex.optimizers)
  parallel     — mesh/collectives/DP/SyncBN/LARC (≡ apex.parallel)
  transformer  — TP/SP/PP library (≡ apex.transformer)
  models       — flagship end-to-end models (ResNet, GPT, MoE-GPT, BERT)
  moe          — expert-parallel Mixture-of-Experts (router/dispatch/layer)
  monitor      — on-device metrics pytree + host sinks + profiler capture
"""

import logging as _logging

__version__ = "0.1.0"


class RankInfoFormatter(_logging.Formatter):
    """Log formatter prefixing (dp, tp, pp) rank info when a mesh is live.

    TPU-native analogue of apex/__init__.py:31-43: instead of torch
    process-group ranks we report jax process_index and, when a global
    mesh has been initialized, the mesh axis coordinates of this host.
    """

    def format(self, record):
        from apex_tpu.parallel import mesh as _mesh

        try:
            info = _mesh.get_rank_info()
        except Exception:
            info = "uninit"
        record.rank_info = info
        return super().format(record)


_logger = _logging.getLogger(__name__)
_logger.addHandler(_logging.NullHandler())


def deprecated_warning(msg: str) -> None:
    """≡ apex.deprecated_warning (apex/__init__.py:45-56): emit a
    deprecation notice once, only from process 0."""
    import warnings

    try:
        import jax

        if jax.process_index() != 0:
            return
    except Exception:
        pass
    warnings.warn(msg, FutureWarning, stacklevel=2)


def _get_logger(name=None):
    return _logging.getLogger(name or __name__)


# Eager, cheap imports only; heavy subpackages import on attribute access.
from apex_tpu import parallel  # noqa: E402,F401
from apex_tpu import ops  # noqa: E402,F401
from apex_tpu import optimizers  # noqa: E402,F401
from apex_tpu import amp  # noqa: E402,F401
from apex_tpu import transformer  # noqa: E402,F401


_LAZY_SUBMODULES = {
    # reference name parity (apex/__init__.py lazy subpackages)
    "contrib", "fp16_utils", "models", "monitor", "normalization", "mlp",
    "fused_dense", "multi_tensor_apply", "checkpoint", "rnn",
    # TPU-native additions
    "moe", "serve", "lint", "tune",
}


def __getattr__(name):
    import importlib

    if name in _LAZY_SUBMODULES:
        return importlib.import_module(f"apex_tpu.{name}")
    if name == "RNN":  # ≡ apex.RNN (apex/RNN/__init__.py)
        return importlib.import_module("apex_tpu.rnn")
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")
