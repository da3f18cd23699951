"""The reference's own pieces: the hand-written expert backward, and weights
drawn again leaf by leaf equal to the tree the program gets."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.reference import revffn
from tiny import DATA


def test_expert_backward_matches_autodiff():
    ref = revffn.RevFFN(json.load(open(DATA / "tiny-moe.json")))
    k = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    T, d, f, E = 24, 16, 8, 5
    x = jax.random.normal(next(k), (T, d))
    comb = jax.random.uniform(next(k), (T, E))
    wg, wu = (jax.random.normal(next(k), (E, d, f)) * 0.3 for _ in range(2))
    wd = jax.random.normal(next(k), (E, f, d)) * 0.3
    ct = jax.random.normal(next(k), (T, d))

    def plain(x, comb, wg, wu, wd):
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) * jnp.einsum(
            "td,edf->tef", x, wu)
        o = jnp.einsum("tef,efd->ted", h, wd)
        return jnp.einsum("te,ted->td", comb, o)

    with jax.default_matmul_precision("highest"):
        want_y, vjp = jax.vjp(plain, x, comb, wg, wu, wd)
        want = vjp(ct)
        got_y, vjp2 = jax.vjp(lambda *a: revffn._experts(ref, *a),
                              x, comb, wg, wu, wd)
        got = vjp2(ct)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("data", ["tiny-moe", "tiny-dense-first"])
def test_leaves_drawn_again_equal_the_tree(data):
    """The program may hold more experts than the reference reads (here
    32, as padding): the reference's are the first ones, bit for bit."""
    ref = revffn.RevFFN(json.load(open(DATA / f"{data}.json")))
    seed = 2**34 + 9
    counts = {l.path: (ref.depth(l),) + (32,) * (len(l.stack) - 1)
              if l.stack else () for l in ref.layout()}
    tree = W.make_tree(seed, ref.layout(), counts, jnp.bfloat16)
    for leaf in ref.layout():
        got = tree
        for p in leaf.path:
            got = got[p]
        got = np.asarray(got.astype(jnp.float32))
        if not leaf.stack:
            np.testing.assert_array_equal(got, ref.draw_top(seed, leaf))
            continue
        for i in range(ref.depth(leaf)):
            want = np.asarray(ref.draw_layer(seed, leaf, i))
            g = got[i]
            if len(leaf.stack) > 1:
                n = ref.count(leaf.stack[1])
                g = g[..., :n] if leaf.experts_last else g[:n]
            np.testing.assert_array_equal(g, want)


def test_seed_beyond_32_bits_changes_the_weights():
    a = W.draw(W.path_key(W.seed_key(5), ("x",)), (4,), "embed", jnp.float32)
    b = W.draw(W.path_key(W.seed_key(5 + 2**31), ("x",)), (4,), "embed",
               jnp.float32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["embed", "fan_in", "small", "norm"])
def test_drawn_values_are_exact_in_bfloat16(kind):
    """No value needs rounding into bfloat16, so a program that keeps the
    draw in float32 gets the bits of one that stores it in bfloat16."""
    k = W.path_key(W.seed_key(2**33 + 3), ("x",))
    a = W.draw(k, (64, 512), kind, jnp.float32)
    b = W.draw(k, (64, 512), kind, jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
