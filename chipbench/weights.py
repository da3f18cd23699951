"""Seeded weights, made on the device.

Every leaf is a function of (seed, path, layer, expert) alone: the harness
makes the program's whole tree in one jitted call, and the reference makes
any one leaf, layer or expert again, bit for bit, without the program.

A leaf of the layout is ``Leaf(path, stack, core, kind)``: ``stack`` names
its leading axes (its stack's "layers", then the experts held, "experts", or
every published expert, "routed"), ``core`` the shape of one slice, and
``kind`` how it is drawn:

  embed    unit scale (rows of about unit RMS, as an embedding table)
  fan_in   1 / sqrt(core[-2])      (a matrix over its fan-in)
  small    0.006                   (adapter down-projections start small)
  router   0.02
  norm     0.1                     (the norm applies 1 + scale)
  bias     0.02

each a centred, nearly normal spread of that scale (``draw``).
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: Tuple[str, ...]
    stack: Tuple[str, ...]          # leading axes, in order
    core: Tuple[int, ...]
    kind: str
    experts_last: bool = False      # the last stack axis sits last in the
    #                                 program's array (a router's columns)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (also beyond 32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def path_key(base, path: Sequence[str]):
    return jax.random.fold_in(base, zlib.crc32("/".join(path).encode())
                              & 0x7FFFFFFF)


def _scale(kind: str, core) -> float:
    if kind == "embed":
        return 1.0
    if kind == "fan_in":
        return 1.0 / math.sqrt(core[-2] if len(core) >= 2 else core[-1])
    return {"small": 0.006, "router": 0.02, "norm": 0.1, "bias": 0.02}[kind]


#: standard deviation of a sum of four uniform 6-bit fields, centred
_FIELDS_STD = math.sqrt(4 * (64 ** 2 - 1) / 12.0)


def draw(key, core, kind: str, dtype):
    """One slice: ``core``-shaped, in ``dtype``, of standard deviation
    ``_scale(kind)`` to within a factor of 2**0.5.

    A value is the centred sum of four 6-bit fields of one random word, an
    integer of magnitude at most 126 and so exact in bfloat16, times a power
    of two.  No step rounds, so every program that draws it gets the same
    bits whatever order of conversions its compiler picks (the TPU compiler
    turned integers above 256 into bfloat16 differently in two programs, and
    a normal drawn through ``erf_inv`` differs in the last place between
    programs that fuse it differently)."""
    bits = jax.random.bits(key, tuple(core), jnp.uint32)
    m = jnp.uint32(63)
    s = ((bits & m) + ((bits >> 8) & m) + ((bits >> 16) & m)
         + ((bits >> 24) & m))
    v = (s.astype(jnp.int32) - 126).astype(dtype)
    p = round(math.log2(_scale(kind, core) / _FIELDS_STD))
    return v * jnp.asarray(2.0 ** p, dtype)


def draw_stacked(key, leaf: Leaf, counts: Sequence[int], dtype,
                 index: Sequence[int] = ()):
    """The leaf with ``counts[i]`` entries along its leading stack axes
    after the fixed ``index``; entry (l, e) is drawn from
    ``fold_in(fold_in(key, l), e)``.  Returns the program's layout (an
    ``experts_last`` leaf has its expert axis moved last)."""
    for i in index:
        key = jax.random.fold_in(key, i)

    def one(k, depth):
        if depth == len(counts):
            return draw(k, leaf.core, leaf.kind, dtype)
        idx = jnp.arange(counts[depth], dtype=jnp.uint32)
        return jax.vmap(lambda i: one(jax.random.fold_in(k, i), depth + 1))(
            idx)
    out = one(key, 0)
    if leaf.experts_last and len(leaf.stack) > len(index):
        out = jnp.moveaxis(out, len(leaf.stack) - len(index) - 1, -1)
    return out


def make_tree(seed: int, leaves: Sequence[Leaf], counts: dict, dtype):
    """The nested dict of every leaf, in one jitted call.  ``counts`` maps
    a path to the stack counts the program holds (it may hold more experts
    than the reference reads, e.g. padding)."""
    leaves = list(leaves)

    def build(key):
        out = {}
        for leaf in leaves:
            val = draw_stacked(path_key(key, leaf.path), leaf,
                               counts[leaf.path], dtype)
            node = out
            for p in leaf.path[:-1]:
                node = node.setdefault(p, {})
            node[leaf.path[-1]] = val
        return out
    return jax.jit(build)(seed_key(seed))
