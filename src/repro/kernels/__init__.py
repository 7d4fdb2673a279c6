"""Egress-path Pallas kernels (Space-Control permission check + memcrypt)
plus shared launch helpers used by every kernel wrapper in this package.

Kernels exist ONLY for the compute hot-spots the paper itself optimizes in
hardware: the permission checker (§4.2.3) and the memory-encryption engine.
Each kernel ships with a pure-jnp oracle in ``ref.py`` and must match it
bit-exactly (see tests/test_kernels.py, tests/test_egress.py).
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default JAX backend is a real TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Backend auto-detection for ``pallas_call(interpret=...)``.

    ``None`` (the default everywhere in this package) means: compile the
    kernel on TPU, fall back to interpreter mode elsewhere — so benchmarks
    measure the real compiled path whenever hardware is present, while CPU
    CI still runs every kernel through the interpreter.
    """
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def compiler_params(interpret: bool, *semantics: str) -> dict:
    """``pallas_call`` keyword arguments for the compiled TPU path: the
    grid's ``dimension_semantics`` (one entry per grid axis, "parallel" or
    "arbitrary").  Interpret mode takes no compiler params, so it gets
    none."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


def bucket_pad(n: int, block: int) -> int:
    """Pad ``n`` up to ``block`` granularity, then bucket the block count to
    the next power of two.

    Every kernel wrapper is jitted with the padded size baked into the
    trace; without bucketing, each distinct batch size triggers a fresh
    trace + compile.  Power-of-two bucketing collapses the shape space to
    O(log n) jit-cache entries at the cost of <2x padding waste.
    """
    if block <= 0:
        raise ValueError("block must be positive")
    blocks = max(1, -(-int(n) // block))
    return (1 << (blocks - 1).bit_length()) * block
