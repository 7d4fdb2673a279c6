"""Public jit'd wrappers for the Pallas kernels.

`use_pallas` selects the Pallas path (auto backend: compiled on TPU,
interpret elsewhere — see ``repro.kernels.resolve_interpret``); the default
is the pure-jnp reference (ref.py), which is what the dry-run lowers so the
512-device host meshes never see Pallas primitives.  A table the kernels
cannot hold (more than ``permcheck.MAX_ENTRIES`` entries) raises on the
Pallas path; it is never answered by the reference instead.
"""
from __future__ import annotations

from . import ref
from .memcrypt import checked_memcrypt_pallas, memcrypt_pallas
from .permcheck import permcheck_pallas


def permission_check(ext_addrs, starts, ends, permbits, *, hwpid: int,
                     need: int, use_pallas: bool = False,
                     mode: str = "hier"):
    """(allowed bool[B], idx i32[B]) — see kernels/permcheck.py."""
    if use_pallas:
        return permcheck_pallas(ext_addrs, starts, ends, permbits,
                                hwpid=hwpid, need=need, mode=mode)
    return ref.permcheck(ext_addrs, starts, ends, permbits,
                         hwpid=hwpid, need=need)


def memory_encrypt(data, *, key0: int, key1: int, base_word: int = 0,
                   use_pallas: bool = False):
    """Counter-mode line cipher; involutive (encrypt == decrypt)."""
    if use_pallas:
        return memcrypt_pallas(data, key0=key0, key1=key1,
                               base_word=base_word)
    return ref.memcrypt(data, key0, key1, base_word)


memory_decrypt = memory_encrypt


def checked_memory_decrypt(data, ext_addrs, starts, ends, permbits, *,
                           hwpid: int, need: int, key0: int, key1: int,
                           base_word: int = 0, use_pallas: bool = False):
    """Fused egress: permission check + decrypt, one kernel launch.

    (out u32[B], fault i32[B]) — denied lanes zeroed, FAULT_* codes emitted.
    See kernels/memcrypt.py (`checked_memcrypt_pallas`) and the matching
    oracle `ref.checked_memcrypt`.
    """
    if use_pallas:
        return checked_memcrypt_pallas(data, ext_addrs, starts, ends,
                                       permbits, hwpid=hwpid, need=need,
                                       key0=key0, key1=key1,
                                       base_word=base_word)
    return ref.checked_memcrypt(data, ext_addrs, starts, ends, permbits,
                                hwpid=hwpid, need=need, key0=key0, key1=key1,
                                base_word=base_word)
