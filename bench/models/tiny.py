"""The service's population-scale FL model, written plainly: 4x4 mean
pooling of a 32x32x3 image to 8x8x3, one 16-unit ReLU layer and a 10-way
linear head (3,258 parameters).

``forward`` takes the operand dtype and matmul precision, so the same code is
the float32 reference (at the configured precision) and its low-precision
control. Its data set is ``images.py``'s.
"""
import jax
import jax.numpy as jnp

from models.images import DATA_SET, IMAGE, make_data  # noqa: F401

POOL = 4


def init(key, cfg, dtype=jnp.float32):
    """He-normal weights and zero biases of ``cfg["param_shapes"]``, one key
    per weight leaf."""
    out = {}
    for i, (name, shape) in enumerate(sorted(cfg["param_shapes"].items())):
        shape = tuple(shape)
        if name.startswith("b"):
            out[name] = jnp.zeros(shape, dtype)
            continue
        w = jax.random.normal(jax.random.fold_in(key, i), shape) * (2.0 / shape[0]) ** 0.5
        out[name] = w.astype(dtype)
    return out


def forward(params, images, *, precision, dtype):
    """images (B, 32, 32, 3) -> logits (B, 10), computed in ``dtype``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    b, h, w, c = images.shape
    x = images.astype(dtype).reshape(b, h // POOL, POOL, w // POOL, POOL, c)
    x = x.mean(axis=(2, 4)).reshape(b, -1)
    x = jax.nn.relu(jnp.dot(x, p["w1"], precision=precision,
                            preferred_element_type=dtype) + p["b1"])
    return jnp.dot(x, p["w2"], precision=precision,
                   preferred_element_type=dtype) + p["b2"]


def forward_flops(cfg) -> int:
    """FLOPs of one image's forward pass: one add per input element for the
    mean pooling, and 2 per MAC of the two dense layers. Biases and ReLU are
    not counted."""
    h, w, c = IMAGE
    fin, f1 = cfg["param_shapes"]["w1"]
    fin2, f2 = cfg["param_shapes"]["w2"]
    return h * w * c + 2 * fin * f1 + 2 * fin2 * f2


def train_flops(cfg) -> int:
    """FLOPs of one image's forward and backward pass: the backward pass
    takes the second layer's input and weight gradients and the first
    layer's weight gradient; nothing needs the first layer's input gradient
    or the pooling's."""
    fin, f1 = cfg["param_shapes"]["w1"]
    fin2, f2 = cfg["param_shapes"]["w2"]
    return forward_flops(cfg) + 2 * (2 * fin2 * f2) + 2 * fin * f1
