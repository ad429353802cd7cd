"""The CIFAR-10-sim data set of the image models, drawn on the device.

Class-conditional Gabor-plus-blob textures with per-image noise and a
per-channel shift, clipped to [0, 1] (the law of the program's
``repro.data.cifar10._synthetic``): 32x32x3 images, one class label each.
"""
import jax
import jax.numpy as jnp
import numpy as np

DATA_SET = "cifar10-sim"
IMAGE = (32, 32, 3)
# fixed, seed-independent class templates, as the program's own
# CIFAR-10-sim keeps them fixed
TEMPLATE_KEY = 1234


def images(key, templ_key, n, classes):
    """(n, 32, 32, 3) float32 images and their (n,) int32 labels."""
    kt = jax.random.split(templ_key, 4)
    angles = jax.random.uniform(kt[0], (classes,), minval=0.0, maxval=np.pi)
    freqs = jax.random.uniform(kt[1], (classes,), minval=3.0, maxval=9.0)
    colors = jax.random.uniform(kt[2], (classes, 3), minval=0.2, maxval=1.0)
    centers = jax.random.uniform(kt[3], (classes, 2), minval=0.25, maxval=0.75)
    yy, xx = jnp.meshgrid(jnp.arange(32, dtype=jnp.float32) / 32.0,
                          jnp.arange(32, dtype=jnp.float32) / 32.0, indexing="ij")
    u = (jnp.cos(angles)[:, None, None] * xx + jnp.sin(angles)[:, None, None] * yy)
    gabor = 0.5 + 0.5 * jnp.sin(2 * np.pi * freqs[:, None, None] * u)
    blob = jnp.exp(-(((xx - centers[:, 0, None, None]) ** 2
                      + (yy - centers[:, 1, None, None]) ** 2) / 0.05))
    base = (0.6 * gabor + 0.4 * blob)[..., None] * colors[:, None, None, :]
    ky, kj, ks = jax.random.split(key, 3)
    y = jax.random.randint(ky, (n,), 0, classes).astype(jnp.int32)
    jitter = 0.25 * jax.random.normal(kj, (n, 32, 32, 3))
    shift = 0.1 * jax.random.normal(ks, (n, 1, 1, 3))
    x = jnp.clip(base[y] + jitter + shift, 0.0, 1.0)
    return x.astype(jnp.float32), y


def make_data(key_train, key_test, cfg):
    """``(x, y, x_test, y_test)``: ``n_train`` and ``n_test`` images of
    ``n_classes`` classes, sharing the fixed class templates."""
    templ = jax.random.PRNGKey(TEMPLATE_KEY)
    x, y = images(key_train, templ, cfg["n_train"], cfg["n_classes"])
    x_test, y_test = images(key_test, templ, cfg["n_test"], cfg["n_classes"])
    return x, y, x_test, y_test
