"""The paper's Section V CNN, written plainly: two 5x5 'SAME' convolutions
(32 and 64 channels), each followed by ReLU and 2x2 max-pooling, a 512-unit
ReLU layer and a 10-way linear head, on 32x32x3 images (NHWC, HWIO).

``forward`` takes the operand dtype and matmul precision, so the same code is
the float32 reference (at the configured precision) and its low-precision
control. Its data set is ``images.py``'s.
"""
import jax
import jax.numpy as jnp

from models.images import DATA_SET, IMAGE, make_data  # noqa: F401


def init(key, cfg, dtype=jnp.float32):
    """He-normal weights and zero biases of ``cfg["param_shapes"]``, one key
    per weight leaf."""
    out = {}
    for i, (name, shape) in enumerate(sorted(cfg["param_shapes"].items())):
        shape = tuple(shape)
        if name.endswith("_b"):
            out[name] = jnp.zeros(shape, dtype)
            continue
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        w = jax.random.normal(jax.random.fold_in(key, i), shape) * (2.0 / fan_in) ** 0.5
        out[name] = w.astype(dtype)
    return out


def forward(params, images, *, precision, dtype):
    """images (B, 32, 32, 3) -> logits (B, 10), computed in ``dtype``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = images.astype(dtype)

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision, preferred_element_type=dtype)
        return y + b

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1_w"], p["conv1_b"])))
    x = pool(jax.nn.relu(conv(x, p["conv2_w"], p["conv2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1_w"], precision=precision,
                            preferred_element_type=dtype) + p["fc1_b"])
    return jnp.dot(x, p["fc2_w"], precision=precision,
                   preferred_element_type=dtype) + p["fc2_b"]


def _taps(size, k):
    """Kernel taps that land inside the input, summed over the output
    positions of one spatial axis of a stride-1 'SAME' convolution."""
    lo = (k - 1) // 2
    return sum(min(size - 1, i - lo + k - 1) - max(0, i - lo) + 1 for i in range(size))


def forward_flops(cfg) -> int:
    """Multiply-add FLOPs (2 per MAC) of one image's forward pass: the two
    'SAME' convolutions at 32x32 and (after the first pool) 16x16, counting
    only the taps that land inside the image (a tap on the zero padding is
    no work the model needs), and the two dense layers. Biases, ReLU and
    pooling are not counted."""
    shapes = cfg["param_shapes"]
    h, w, _ = IMAGE
    kh, kw, cin, c1 = shapes["conv1_w"]
    conv1 = 2 * _taps(h, kh) * _taps(w, kw) * cin * c1
    kh, kw, cin, c2 = shapes["conv2_w"]
    conv2 = 2 * _taps(h // 2, kh) * _taps(w // 2, kw) * cin * c2
    fin, f1 = shapes["fc1_w"]
    fin2, f2 = shapes["fc2_w"]
    return conv1 + conv2 + 2 * fin * f1 + 2 * fin2 * f2


def train_flops(cfg) -> int:
    """FLOPs of one image's forward and backward pass: the backward pass
    takes two of each layer's forward FLOPs (input and weight gradients),
    except the first convolution, whose input gradient nothing needs."""
    kh, kw, cin, c1 = cfg["param_shapes"]["conv1_w"]
    conv1 = 2 * _taps(IMAGE[0], kh) * _taps(IMAGE[1], kw) * cin * c1
    return 3 * forward_flops(cfg) - conv1
