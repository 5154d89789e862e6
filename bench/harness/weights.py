"""Seeded weights, made by the benchmark and handed to the program.

Every value is an integer in [-32768, 32767] drawn from threefry bits, times
one float32 constant, rounded to the served dtype. Integer draws and a single
multiply round the same in every program, so the weights of layer ``i``
regenerated on their own (for the reference, which runs layer by layer) are
bit-for-bit the weights that the whole-model call made for the program.

A spec maps a path of the parameter tree to ``(shape, std, per_layer)``;
``per_layer`` leaves carry the layer count as their leading dimension.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any non-negative seed: ``PRNGKey`` keeps 32 bits only."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf_keys(spec: dict, key):
    return {path: jax.random.fold_in(key, i)
            for i, path in enumerate(sorted(spec))}


def _draw(key, shape, std: float, dtype):
    ints = (jax.random.bits(key, shape, jnp.uint32) >> 16).astype(jnp.int32)
    scale = jnp.float32(std * math.sqrt(3.0) / 32768.0)
    return ((ints - 32768).astype(jnp.float32) * scale).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


def make_params(spec: dict, seed: int, num_layers: int, dtype):
    """The whole tree in one jitted call, on the default device."""

    def build(key):
        keys = _leaf_keys(spec, key)
        flat = {}
        for path, (shape, std, per_layer) in spec.items():
            if per_layer:
                lkeys = jax.vmap(lambda i, k=keys[path]: jax.random.fold_in(
                    k, i))(jnp.arange(num_layers))
                flat[path] = jax.vmap(
                    lambda k, s=tuple(shape[1:]), sd=std: _draw(k, s, sd,
                                                                dtype))(lkeys)
            else:
                flat[path] = _draw(keys[path], tuple(shape), std, dtype)
        return _nest(flat)

    return jax.jit(build)(base_key(seed))


def layer_params(spec: dict, seed: int, layer: int, dtype) -> dict:
    """Layer ``layer``'s leaves, as ``make_params`` made them."""
    key = base_key(seed)
    out = {}
    for path, (shape, std, per_layer) in spec.items():
        if per_layer:
            out[path] = _draw_layer(path, spec, key, layer, tuple(shape[1:]),
                                    std, dtype)
    return out


def leaf(spec: dict, seed: int, path: tuple, dtype):
    """One whole leaf that has no layer dimension."""
    shape, std, per_layer = spec[path]
    assert not per_layer, path
    keys = _leaf_keys(spec, base_key(seed))
    return jax.jit(_draw, static_argnums=(1, 2, 3))(keys[path], tuple(shape),
                                                    std, dtype)


def _draw_layer(path, spec, key, layer, shape, std, dtype):
    k = jax.random.fold_in(_leaf_keys(spec, key)[path], layer)
    return jax.jit(_draw, static_argnums=(1, 2, 3))(k, shape, std, dtype)
