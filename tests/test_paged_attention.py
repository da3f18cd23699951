"""Paged gather-attention kernel: interpret-mode Pallas vs the gather-jax
reference, page-table indirection (permutation invariance, unmapped pages),
and the validity masking that makes pool remapping safe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention as pk


def _scenario(B=3, n_pages=4, page=8, KV=2, G=2, hd=16, extra_pages=3,
              seed=0, dtype=jnp.float32):
    """Random paged decode state: per-slot position t_b, a shuffled
    page-table mapping, positions valid only below t_b (decode has not
    written slot t yet — matches the engine, where the query attends to the
    cache BEFORE its own K/V write lands)."""
    rng = np.random.default_rng(seed)
    H = KV * G
    kv_len = n_pages * page
    P = B * n_pages + extra_pages
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(P, page, KV, hd)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(P, page, KV, hd)), dtype)
    t = rng.integers(1, kv_len, size=B).astype(np.int32)

    phys = rng.permutation(P)
    pt = np.full((B, n_pages), -1, np.int32)
    pos = np.full((P, page), -1, np.int32)
    for b in range(B):
        n_map = -(-int(t[b] + 1) // page)          # pages holding pos <= t
        for j in range(min(n_map, n_pages)):
            pp = int(phys[b * n_pages + j])
            pt[b, j] = pp
            base = j * page
            for o in range(page):
                if base + o <= t[b]:
                    pos[pp, o] = base + o
    return (q, k_pool, v_pool, jnp.asarray(pos), jnp.asarray(pt),
            jnp.asarray(t), kv_len)


@pytest.mark.parametrize("window,softcap", [(None, None), (11, None),
                                            (None, 30.0), (7, 30.0)])
def test_pallas_interpret_matches_jax_reference(window, softcap):
    q, k, v, pos, pt, t, kv_len = _scenario(seed=hash((window, softcap)) % 97)
    ref = pk.paged_attention(q, k, v, pos, pt, t, kv_len=kv_len,
                             window=window, softcap=softcap, impl="jax")
    out = pk.paged_attention(q, k, v, pos, pt, t, kv_len=kv_len,
                             window=window, softcap=softcap, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_page_permutation_invariance():
    """Remapping every logical page to different physical pages (same
    content) must not change the output — the whole point of the table."""
    q, k, v, pos, pt, t, kv_len = _scenario(seed=5)
    base = pk.paged_attention_jax(q, k, v, pos, pt, t, kv_len=kv_len)

    P = k.shape[0]
    perm = np.random.default_rng(9).permutation(P)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(P)
    k2, v2, pos2 = k[perm], v[perm], pos[perm]
    pt2 = jnp.where(pt >= 0, jnp.asarray(inv)[jnp.clip(pt, 0, P - 1)], -1)
    moved = pk.paged_attention_jax(q, k2, v2, pos2, pt2, t, kv_len=kv_len)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))


def test_unmapped_pages_and_stale_positions_masked():
    """Pages past the mapped prefix (-1 entries) may hold arbitrary garbage
    — including VALID-looking positions from a previous owner — and must
    not leak into the output; same for mapped pages' pos = -1 rows."""
    q, k, v, pos, pt, t, kv_len = _scenario(seed=11)
    base = pk.paged_attention_jax(q, k, v, pos, pt, t, kv_len=kv_len)

    # poison every UNmapped physical page with in-range positions
    mapped = set(int(x) for x in np.asarray(pt).ravel() if x >= 0)
    pos2 = np.asarray(pos).copy()
    for p in range(k.shape[0]):
        if p not in mapped:
            pos2[p] = np.arange(pos2.shape[1])
    out = pk.paged_attention_jax(q, k, v, jnp.asarray(pos2), pt, t,
                                 kv_len=kv_len)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))

    # pallas path must mask identically
    pal = pk.paged_attention(q, k, v, jnp.asarray(pos2), pt, t,
                             kv_len=kv_len, impl="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_gather_pages_layout():
    """gather_pages flattens the page table into the logical buffer order
    and surfaces unmapped pages as pos = -1."""
    q, k, v, pos, pt, t, kv_len = _scenario(B=2, seed=3)
    gk, gv, gpos = pk.gather_pages(k, v, pos, pt, kv_len)
    assert gk.shape == (2, kv_len) + k.shape[2:]
    page = k.shape[1]
    ptn = np.asarray(pt)
    for b in range(2):
        for j in range(ptn.shape[1]):
            sl = np.asarray(gpos[b, j * page:(j + 1) * page])
            if ptn[b, j] < 0:
                assert (sl == -1).all()
            else:
                np.testing.assert_array_equal(
                    sl, np.asarray(pos[ptn[b, j]]))
                np.testing.assert_array_equal(
                    np.asarray(gk[b, j * page:(j + 1) * page]),
                    np.asarray(k[ptn[b, j]]))


def test_traced_window_routes_to_jax_path():
    """local/global schedules pass a traced window scalar; the wrapper must
    fall back to the gather-jax path instead of tracing the kernel."""
    q, k, v, pos, pt, t, kv_len = _scenario(seed=13)

    @jax.jit
    def run(w):
        return pk.paged_attention(q, k, v, pos, pt, t, kv_len=kv_len,
                                  window=w)
    out = run(jnp.int32(9))
    ref = pk.paged_attention_jax(q, k, v, pos, pt, t, kv_len=kv_len,
                                 window=9)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
