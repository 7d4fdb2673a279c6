"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) must match
the pure-jnp oracle in ref.py bit-exactly (integer kernels) / to float
tolerance (flash attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.table import HWPID_SHIFT
from repro.kernels import ops, ref
from repro.kernels.memcrypt import memcrypt_pallas
from repro.kernels.permcheck import MAX_ENTRIES, permcheck_pallas


def _mk_table(rng, n_entries, sdm_pages):
    """Random sorted non-overlapping ranges + per-entry 2-bit perms."""
    bounds = np.sort(rng.choice(sdm_pages, size=2 * n_entries, replace=False))
    starts = bounds[0::2].astype(np.int32)
    ends = bounds[1::2].astype(np.int32)
    perms = rng.integers(0, 4, n_entries).astype(np.uint32)
    return starts, ends, perms


# ---------------------------------------------------------------------------
# permcheck kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 7, 128, 1024, 1500])
@pytest.mark.parametrize("n_entries", [1, 5, 100])
def test_permcheck_matches_ref_shapes(rng, batch, n_entries):
    sdm_pages = 1 << 16
    starts, ends, perms = _mk_table(rng, n_entries, sdm_pages)
    hwpid = 3
    pages = rng.integers(0, sdm_pages, batch).astype(np.int32)
    tags = rng.choice([hwpid, hwpid, 0, 5], batch).astype(np.int32)
    ext = (tags << HWPID_SHIFT) | pages
    for need in (1, 2, 3):
        a_p, i_p = permcheck_pallas(jnp.asarray(ext), jnp.asarray(starts),
                                    jnp.asarray(ends), jnp.asarray(perms),
                                    hwpid=hwpid, need=need, interpret=True)
        a_r, i_r = ref.permcheck(jnp.asarray(ext), jnp.asarray(starts),
                                 jnp.asarray(ends), jnp.asarray(perms),
                                 hwpid=hwpid, need=need)
        np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))
        # idx only defined where a range covers the page
        cover = np.asarray(i_r) >= 0
        np.testing.assert_array_equal(np.asarray(i_p)[cover],
                                      np.asarray(i_r)[cover])


def test_permcheck_denies_wrong_tag(rng):
    starts = np.asarray([0], np.int32)
    ends = np.asarray([1000], np.int32)
    perms = np.asarray([3], np.uint32)
    pages = np.arange(64, dtype=np.int32)
    ext = (np.int32(9) << HWPID_SHIFT) | pages
    allowed, _ = permcheck_pallas(jnp.asarray(ext), jnp.asarray(starts),
                                  jnp.asarray(ends), jnp.asarray(perms),
                                  hwpid=4, need=1, interpret=True)
    assert not bool(np.asarray(allowed).any())


def test_permcheck_entry_tile_boundary(rng):
    """Entry counts straddling the 1024-entry tile size."""
    sdm_pages = 1 << 20
    for n_entries in (1023, 1024, 1025, 2048):
        starts, ends, perms = _mk_table(rng, n_entries, sdm_pages)
        pages = rng.integers(0, sdm_pages, 256).astype(np.int32)
        ext = (np.int32(1) << HWPID_SHIFT) | pages
        a_p, i_p = permcheck_pallas(jnp.asarray(ext), jnp.asarray(starts),
                                    jnp.asarray(ends), jnp.asarray(perms),
                                    hwpid=1, need=1, interpret=True)
        a_r, i_r = ref.permcheck(jnp.asarray(ext), jnp.asarray(starts),
                                 jnp.asarray(ends), jnp.asarray(perms),
                                 hwpid=1, need=1)
        np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))


def test_permcheck_capacity_guard(rng):
    starts = np.zeros(MAX_ENTRIES + 1, np.int32)
    with pytest.raises(ValueError):
        permcheck_pallas(jnp.zeros((8,), jnp.int32), jnp.asarray(starts),
                         jnp.asarray(starts), jnp.zeros(MAX_ENTRIES + 1,
                                                        jnp.uint32),
                         hwpid=1, need=1, interpret=True)


def test_ops_pallas_path_raises_beyond_max_entries():
    """A table the kernels cannot hold raises on the Pallas path; the
    reference never answers in the kernel's place."""
    z = jnp.zeros((MAX_ENTRIES + 1,), jnp.int32)
    pb = jnp.zeros((MAX_ENTRIES + 1,), jnp.uint32)
    ext = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError):
        ops.permission_check(ext, z, z, pb, hwpid=1, need=1, use_pallas=True)
    with pytest.raises(ValueError):
        ops.checked_memory_decrypt(jnp.zeros((8,), jnp.uint32), ext, z, z, pb,
                                   hwpid=1, need=1, key0=1, key1=2,
                                   use_pallas=True)


def test_ops_dispatcher_consistency(rng):
    starts, ends, perms = _mk_table(rng, 64, 1 << 16)
    pages = rng.integers(0, 1 << 16, 100).astype(np.int32)
    ext = (np.int32(2) << HWPID_SHIFT) | pages
    a1, _ = ops.permission_check(jnp.asarray(ext), jnp.asarray(starts),
                                 jnp.asarray(ends), jnp.asarray(perms),
                                 hwpid=2, need=1, use_pallas=True)
    a2, _ = ops.permission_check(jnp.asarray(ext), jnp.asarray(starts),
                                 jnp.asarray(ends), jnp.asarray(perms),
                                 hwpid=2, need=1, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


# ---------------------------------------------------------------------------
# memcrypt kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16,), (1000,), (8, 128), (3, 5, 7),
                                   (1024,), (4096,), (2, 1024)])
def test_memcrypt_matches_ref(rng, shape):
    data = rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)
    k0, k1 = 0xDEADBEEF, 0x12345678
    enc_p = memcrypt_pallas(jnp.asarray(data), key0=k0, key1=k1,
                            interpret=True)
    enc_r = ref.memcrypt(jnp.asarray(data), k0, k1)
    np.testing.assert_array_equal(np.asarray(enc_p), np.asarray(enc_r))


def test_memcrypt_involution(rng):
    data = rng.integers(0, 1 << 32, size=(777,), dtype=np.uint32)
    k0, k1 = 7, 9
    enc = memcrypt_pallas(jnp.asarray(data), key0=k0, key1=k1, interpret=True)
    dec = memcrypt_pallas(enc, key0=k0, key1=k1, interpret=True)
    np.testing.assert_array_equal(np.asarray(dec), data)
    assert not np.array_equal(np.asarray(enc), data)


def test_memcrypt_keys_matter(rng):
    data = rng.integers(0, 1 << 32, size=(256,), dtype=np.uint32)
    a = memcrypt_pallas(jnp.asarray(data), key0=1, key1=2, interpret=True)
    b = memcrypt_pallas(jnp.asarray(data), key0=1, key1=3, interpret=True)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_memcrypt_base_word_offset(rng):
    """Encrypting a buffer in two halves with the right base offsets must
    equal encrypting it at once (streaming encryption of cache lines)."""
    data = rng.integers(0, 1 << 32, size=(2048,), dtype=np.uint32)
    whole = np.asarray(ref.memcrypt(jnp.asarray(data), 5, 6))
    lo = np.asarray(memcrypt_pallas(jnp.asarray(data[:1024]), key0=5, key1=6,
                                    base_word=0, interpret=True))
    hi = np.asarray(memcrypt_pallas(jnp.asarray(data[1024:]), key0=5, key1=6,
                                    base_word=1024, interpret=True))
    np.testing.assert_array_equal(np.concatenate([lo, hi]), whole)


def test_memcrypt_ciphertext_unreadable():
    """The §5.1.2 scenario: an OS that aliases a trusted page reads only
    ciphertext — keystream without the key looks uniform (weak sanity:
    byte histogram not concentrated)."""
    data = np.zeros(4096, np.uint32)  # all-zero plaintext
    enc = np.asarray(memcrypt_pallas(jnp.asarray(data), key0=0xAA, key1=0xBB,
                                     interpret=True))
    assert len(np.unique(enc)) > 3500  # ~uniform, no structure leaks
