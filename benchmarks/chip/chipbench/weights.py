"""Seeded weights, made on the device in one compiled call.

A reference module's ``weight_specs(conf)`` names every weight of its
canonical layout with its shape and how it is drawn.  ``generate`` draws
them all from the seed in the type they are served in; the benchmark hands
the program its own layout of the same numbers (an adapter's
``program_params``), and the reference draws them again with the same call
after the program's state is freed.  Each leaf's stream depends only on the
seed, the leaf's name and (for ``layers/`` leaves) the layer index.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Any whole number below 2**62 as two uint32 words."""
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"seed {seed} outside [0, 2**62)")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


# the version of the weight stream: a change to how weights are drawn
# changes it, so that no two versions read the same seed alike
STREAM = 0


def _key(words, name: str):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    key = jax.random.fold_in(key, STREAM)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind: str, std: float, dtype):
    z = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "scale":
        z = 1.0 + z
    elif kind != "normal":
        raise ValueError(kind)
    return z.astype(dtype)


def draw_all(words, specs: Dict, dtype) -> Dict:
    """Traceable: the canonical weights as a nested dict (``layers/x`` ->
    ``out["layers"]["x"]``)."""
    out: Dict = {}
    for name, (shape, kind, std) in specs.items():
        key = _key(words, name)
        if name.startswith("layers/"):
            layer_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(shape[0]))
            leaf = jax.vmap(lambda k: _draw(k, shape[1:], kind, std, dtype))(
                layer_keys)
            out.setdefault("layers", {})[name[7:]] = leaf
        else:
            out[name] = _draw(key, shape, kind, std, dtype)
    return out


def generator(specs: Dict, dtype=jnp.bfloat16, *,
              adapt: Optional[Callable] = None, shardings=None
              ) -> Callable[[int], Dict]:
    """``seed -> weights``: all of them in one compiled call.  ``adapt``
    maps the canonical dict to another layout inside the same call;
    ``shardings`` (a pytree matching the output) places every leaf on its
    shards as it is born.  Keep the returned function to reuse its
    compiled program across seeds."""
    specs = dict(specs)

    def make(words):
        canon = draw_all(words, specs, dtype)
        return adapt(canon) if adapt is not None else canon

    fn = jax.jit(make, out_shardings=shardings)
    return lambda seed: fn(jnp.asarray(seed_words(seed)))
