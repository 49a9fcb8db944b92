"""Random weights from a run's seed, made by the benchmark and not by the
program.

``make_params`` fills the program's parameter tree (its structure is the
interface through which weights are loaded, as a checkpoint would be)
in one jitted call on the device, in the types the tree declares.
``LayerWeights`` regenerates one layer's leaves, bit for bit, for the
plain reference, which never sees the program's arrays.

Every leaf draws from ``fold_in(fold_in(key(seed), leaf), layer)``, so a
layer can be made alone. The rule for a leaf follows its name:

  tok          N(0, 0.02^2); rows past the vocabulary are 0, as a loader
               pads a published embedding
  w            N(0, 1/fan_in)               dense projection (in, out)
  blocks       N(0, 1/(r * b))              pixelfly nonzero blocks (nb_out, r, b, b)
  U, V         N(0, 1/in), N(0, 1/rank)     pixelfly low-rank factors
  gamma        0.5                          pixelfly mixing weight
  scale, q_norm, k_norm   1 + N(0, 0.1^2)   norm scales
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["base_key", "leaf_paths", "make_params", "LayerWeights"]


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones wider than
    32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def leaf_paths(shapes) -> list[tuple[str, ...]]:
    """Leaf paths of a parameter tree, as tuples of key names, in the
    tree's flattening order (the leaf index used for its key)."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return [
        tuple(str(getattr(p, "key", p)) for p in path) for path, _ in flat
    ]


def _stacked(path: tuple[str, ...]) -> bool:
    """Leaves under ``groups`` carry a leading layer axis."""
    return path[0] == "groups"


def _one(key, name: str, shape, dtype, vocab: int):
    """One layer's value of a leaf named ``name`` with ``shape``."""
    normal = lambda std: jax.random.normal(key, shape, jnp.float32) * std  # noqa: E731
    if name == "tok":
        rows = jnp.arange(shape[0])[:, None] < vocab
        val = jnp.where(rows, normal(0.02), 0.0)
    elif name == "w":
        val = normal(1.0 / math.sqrt(shape[-2]))
    elif name == "blocks":
        val = normal(1.0 / math.sqrt(shape[-3] * shape[-1]))
    elif name == "U":
        val = normal(1.0 / math.sqrt(shape[-2]))
    elif name == "V":
        val = normal(1.0 / math.sqrt(shape[-1]))
    elif name == "gamma":
        val = jnp.full(shape, 0.5, jnp.float32)
    elif name in ("scale", "q_norm", "k_norm"):
        val = 1.0 + normal(0.1)
    elif name == "b":
        val = jnp.zeros(shape, jnp.float32)
    else:
        raise KeyError(f"no weight rule for a leaf named {name!r}")
    return val.astype(dtype)


def make_params(shapes, seed: int, vocab: int, shardings=None):
    """The whole tree in one jitted call, on the default device or, with
    ``shardings`` (a matching tree), made where those place each leaf.
    ``shapes`` is the program's tree of ``ShapeDtypeStruct``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = leaf_paths(shapes)

    def build(key):
        out = []
        for i, ((path, sds), p) in enumerate(zip(flat, paths)):
            k = jax.random.fold_in(key, i)
            name = _name(path)
            if _stacked(p):
                layers = jnp.arange(sds.shape[0])
                out.append(
                    jax.vmap(
                        lambda li: _one(
                            jax.random.fold_in(k, li), name, sds.shape[1:],
                            sds.dtype, vocab,
                        )
                    )(layers)
                )
            else:
                out.append(
                    _one(jax.random.fold_in(k, 0), name, sds.shape, sds.dtype,
                         vocab)
                )
        return jax.tree_util.tree_unflatten(treedef, out)

    placed = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(build, **placed)(base_key(seed))


class LayerWeights:
    """Float32 copies of the leaves of one layer of a stacked group, or of
    the unstacked leaves, keyed by path (a layer's without the
    ``groups/<group>`` prefix). Values equal those ``make_params`` gives
    in their declared type, then upcast. One compiled builder serves
    every layer of a group."""

    def __init__(self, shapes, seed: int, vocab: int):
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        paths = leaf_paths(shapes)
        self._key = base_key(seed)
        # the stacked groups, in the tree's order
        self.groups = list(dict.fromkeys(p[1] for p in paths if _stacked(p)))
        self._layer = {
            g: jax.jit(self._builder(flat, paths, vocab, group=g)) for g in self.groups
        }
        self._top = jax.jit(self._builder(flat, paths, vocab, group=None))

    @staticmethod
    def _builder(flat, paths, vocab, *, group: str | None):
        """Leaves of ``group``'s layers, or with None the unstacked ones;
        each keeps the key of its index among all the tree's leaves."""
        picks = [
            (i, p, sds)
            for i, (p, (_, sds)) in enumerate(zip(paths, flat))
            if (p[1] == group if _stacked(p) else group is None)
        ]

        def build(key, li):
            out = {}
            for i, p, sds in picks:
                k = jax.random.fold_in(key, i)
                shape, name = (sds.shape[1:], p[2:]) if group else (sds.shape, p)
                val = _one(jax.random.fold_in(k, li), p[-1], shape, sds.dtype, vocab)
                out["/".join(name)] = val.astype(jnp.float32)
            return out

        return build

    def layer(self, index: int, group: str | None = None) -> dict:
        """Layer ``index`` of ``group``; without a group, of the tree's
        only stacked group."""
        if group is None:
            if len(self.groups) != 1:
                raise ValueError(f"name a group: the tree has {self.groups}")
            group = self.groups[0]
        return self._layer[group](self._key, jnp.int32(index))

    def top(self) -> dict:
        return self._top(self._key, jnp.int32(0))


def tree_bytes(tree) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(tree)))
