"""The benchmark's seeded MNIST stand-in, made on the device in one call.

A copy of the class-prototype generator behind the program's
``data.mnist_like`` (28x28 images, 10 classes, smooth random
prototypes, translation jitter of up to 4 pixels, smooth and white
noise, squashed into [0, 1]), rewritten over ``jax.random`` so that the
whole data set comes from ``--seed`` in one jitted call on the chip
instead of from NumPy on the host. The draws differ from the program's
generator; the distribution is the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SIDE = 28
NUM_CLASSES = 10
PROTO_SCALE = 2.0
NOISE_SCALE = 0.8
MAX_SHIFT = 4


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, all of its bits used."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _smooth_noise(key, n, scale):
    """Low-frequency noise: a coarse (SIDE/4)^2 grid upsampled 4x."""
    coarse = jax.random.normal(key, (n, SIDE // 4, SIDE // 4)) * scale
    return jnp.repeat(jnp.repeat(coarse, 4, axis=1), 4, axis=2)


def _draw(protos, key, n):
    ky, kr, kc, ks, kw = jax.random.split(key, 5)
    y = jax.random.randint(ky, (n,), 0, NUM_CLASSES, jnp.int32)
    dr = jax.random.randint(kr, (n, 1), -MAX_SHIFT, MAX_SHIFT + 1)
    dc = jax.random.randint(kc, (n, 1), -MAX_SHIFT, MAX_SHIFT + 1)
    pos = jnp.arange(SIDE)[None]
    rows = (pos - dr) % SIDE                     # np.roll along rows
    cols = (pos - dc) % SIDE                     # np.roll along columns
    x = protos[y]
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    x = jnp.take_along_axis(x, cols[:, None, :], axis=2)
    x = x + _smooth_noise(ks, n, NOISE_SCALE)
    x = x.reshape(n, SIDE * SIDE)
    x = x + jax.random.normal(kw, x.shape) * (NOISE_SCALE * 0.5)
    return jax.nn.sigmoid(x).astype(jnp.float32), y


@functools.partial(jax.jit, static_argnames=("n_train", "n_test"))
def mnist_like(key, *, n_train, n_test):
    """(x_train, y_train, x_test, y_test): x (n, 784) f32 in [0, 1],
    y (n,) int32, all drawn from ``key``."""
    kp, ktr, kte = jax.random.split(key, 3)
    protos = _smooth_noise(kp, NUM_CLASSES, PROTO_SCALE)
    x_tr, y_tr = _draw(protos, ktr, n_train)
    x_te, y_te = _draw(protos, kte, n_test)
    return x_tr, y_tr, x_te, y_te
