"""Pallas TPU kernels for the framework's compute hot-spots.

  bitplane_gemm — bit-serial -> bit-plane int GEMM on the MXU
  flash_attention — online-softmax attention forward

Each kernel has a pure-jnp oracle in ref.py (which also holds the MVE
vsld/vsst multi-dim strided gather/scatter semantics, ``mdgather_ref`` /
``mdscatter_ref``); ops.py holds the jit'd dispatch wrappers the models
call, and platform.py the one interpret-mode decision.

Submodules import lazily (PEP 562, like :mod:`repro.core`): ``ref``
holds only pure-jnp oracles and is what :mod:`repro.nn` validates
against, while ``ops`` pulls in the Pallas TPU kernel modules — eager
import here would drag the TPU lowering stack into CPU-only consumers.
"""
_LAZY = {"ops", "ref", "platform", "bitplane_gemm", "flash_attention"}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | _LAZY)
