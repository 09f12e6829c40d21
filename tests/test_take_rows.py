"""`engine._take_rows` moves each row as 32-bit words: bit-exact against a
per-column gather of the int64 columns its three callers used to pack
(extraction, the global merge, the islands route), out-of-range indices
clipped as before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core import engine, soa, simtime

pytestmark = pytest.mark.quick

I32 = np.iinfo(np.int32)
I64 = np.iinfo(np.int64)
N = 61


def _fields(rng, P):
    """Row fields over the word split's edge values: NEVER, negative
    int64, set high bits, int32 extremes."""
    t = rng.integers(I64.min, I64.max, N, dtype=np.int64, endpoint=True)
    t[:8] = [simtime.NEVER, -1, I64.min, 0, 1 << 31, (1 << 32) - 1,
             -(1 << 32), (1 << 62) | 0xFFFFFFFF]
    ints = []
    for _ in range(4):  # dst, src, seq, kind
        c = rng.integers(I32.min, I32.max, N, dtype=np.int32, endpoint=True)
        c[:4] = [I32.min, I32.max, -1, 0]
        ints.append(c)
    pay = rng.integers(I32.min, I32.max, (N, P), dtype=np.int32,
                       endpoint=True)
    pay[0] = I32.min
    pay[1] = I32.max
    pay[2] = -1
    packed = soa.pack_words(jnp.asarray(pay))
    p = [packed[:, w] for w in range(packed.shape[1])]
    return jnp.asarray(t), *(jnp.asarray(c) for c in ints), p


def _unpack2(x):
    return (x >> 32).astype(jnp.int32), x.astype(jnp.int32)


# call site: (time rides along?, the int32 pairs it packed into int64
# columns before, in column order, rows gathered); payload columns follow
# in both forms
LAYOUTS = {
    # _dense_extract: time, the src|seq sort key k2, dst|kind; every row
    "extract": (True, ("sq", "dk"), N),
    # the global merge's assemble: dst|kind, src|seq; truncated to the pool
    "merge": (False, ("dk", "sq"), N - 9),
    # _island_route: time, dst|kind, src|seq; every row
    "islands": (True, ("dk", "sq"), N),
}


@pytest.mark.parametrize("P", [1, 13], ids=["PP1", "PP7"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_take_rows_matches_int64_column_gathers(layout, P):
    timed, pairs, rows = LAYOUTS[layout]
    rng = np.random.default_rng(P * 1009 + len(layout))
    t, d, s, q, k, p = _fields(rng, P)
    # row indices with repeats, and out of range on both sides for
    # mode="clip"
    perm = rng.integers(0, N, rows, dtype=np.int32)
    perm[:5] = [-1, N, N + 7, I32.min, I32.max]
    perm = jnp.asarray(perm)
    head = [t] if timed else []
    got = jax.jit(engine._take_rows)(head + [d, s, q, k] + p, perm)

    def take(c):
        return jnp.take(c, perm, axis=0, mode="clip")

    packed = {"dk": engine._pack2(d, k), "sq": engine._pack2(s, q)}
    old = {n: _unpack2(take(packed[n])) for n in pairs}
    (wd, wk), (ws, wq) = old["dk"], old["sq"]
    want = [take(c) for c in head] + [wd, ws, wq, wk] + [take(c) for c in p]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
