"""ops/lexsort.lex_order: the permutation of a stable multi-key
`lax.sort`, built from single-key passes (bit-identical, ties included)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yugabyte_db_tpu.ops.lexsort import lex_order


@pytest.mark.parametrize("n", [1, 2, 7, 4096, 50_000])
def test_matches_stable_multi_key_sort(n):
    rng = np.random.default_rng(n)
    # few distinct values per key: long runs of ties at every level, and
    # values that differ only in the high or only in the low u32 word
    keys = (
        rng.integers(0, 5, n).astype(np.uint64) * np.uint64(0x1_0000_0001),
        rng.integers(0, 2, n).astype(np.uint8),
        rng.integers(0, 3, n).astype(np.uint64) << np.uint64(40),
        rng.integers(0, 3, n).astype(np.uint32),
    )
    keys = tuple(jnp.asarray(k) for k in keys)
    want = jax.lax.sort(keys + (jnp.arange(n, dtype=jnp.int32),),
                        num_keys=len(keys))[-1]
    got = jax.jit(lex_order)(keys)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_signed_bool_and_float_keys_keep_lax_sort_order():
    rng = np.random.default_rng(3)
    n = 5000
    special = np.array([0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf,
                        1.5, -1.5, 1e-40, -1e-40])
    keys = (jnp.asarray(rng.random(n) < 0.5),
            jnp.asarray(rng.integers(-3, 3, n).astype(np.int32)),
            jnp.asarray(special[rng.integers(0, len(special), n)]
                        .astype(np.float32)),
            jnp.asarray(rng.integers(-2, 2, n).astype(np.int64) << 40),
            jnp.asarray(special[rng.integers(0, len(special), n)]))
    want = jax.lax.sort(keys + (jnp.arange(n, dtype=jnp.int32),),
                        num_keys=len(keys))[-1]
    np.testing.assert_array_equal(np.asarray(lex_order(keys)),
                                  np.asarray(want))


def test_rejects_keys_without_an_order():
    with pytest.raises(TypeError):
        lex_order((jnp.zeros(4, jnp.complex64),))
