"""The training traffic: seeded synthetic batches, made on the device.

One generator per data kind, read from a mix's ``data`` block.  A batch is
a pure function of (seed, step, shard), so a seed gives the same inputs in
every run and every chip count, and the reference can make them again
after the window.

``gaussian_image``: CIFAR-shaped class-conditional Gaussian images, the
program's ``GaussianImageTask`` copied here so that the benchmark owns its
traffic: fixed class means from ``means_seed``, ``snr`` times the class
mean plus unit noise, labels uniform over the classes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def gaussian_image(data: Dict[str, Any], arch: Dict[str, Any], seed: int,
                   batch: int) -> Callable[[int, int], Dict[str, jnp.ndarray]]:
    hw, ch, classes = arch["image_hw"], arch["channels"], arch["classes"]
    rng = np.random.RandomState(data["means_seed"])
    means = jnp.asarray(rng.randn(classes, hw, hw, ch).astype(np.float32))
    snr = float(data["snr"])

    @jax.jit
    def make(seed, step, shard):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(seed), step), shard)
        k0, k1 = jax.random.split(key)
        labels = jax.random.randint(k0, (batch,), 0, classes)
        noise = jax.random.normal(k1, (batch, hw, hw, ch))
        return {"image": snr * means[labels] + noise, "label": labels}

    # the seed is an argument, not a constant, so every seed runs one
    # compiled generator
    return lambda step, shard: make(np.uint32(seed), np.int32(step),
                                    np.int32(shard))


GENERATORS = {"gaussian_image": gaussian_image}


def make_batches(data: Dict[str, Any], arch: Dict[str, Any], seed: int,
                 batch: int):
    """``make(step, shard) -> batch`` for the mix's data kind."""
    kind = data["kind"]
    if kind not in GENERATORS:
        raise ValueError(f"unknown data kind {kind!r}; known: "
                         f"{sorted(GENERATORS)}")
    return GENERATORS[kind](data, arch, seed, batch)
