"""A toy language model with a frozen base and LoRA adapters, written plainly.

It exists to show that a model comes into the benchmark as files only: it
lives outside ``bench/models`` and joins the ``models`` namespace when its
directory (``bench/toy``) is on the path. ``bench/test_harness.py`` runs it
through ``world.py``, ``reference.py`` and ``flops.py``.

The base: token embedding, ``n_layers`` pre-norm blocks (parameter-free RMS
norm, causal multi-head attention, a GELU MLP), a final RMS norm and an
untied head over the vocabulary. The trained tree: rank-``lora_rank``
adapters ``A @ B * lora_alpha / lora_rank`` on each block's query and value
projections, ``B`` starting at zero. The data: Zipf-Markov token sequences,
the next token the previous one's fixed offset plus a Zipf-distributed step,
drawn in JAX; ``y`` is ``x`` shifted by one (next-token labels).
"""
import jax
import jax.numpy as jnp

DATA_SET = "zipf-markov"
# fixed, seed-independent transition offsets, shared by train and test data
TEMPLATE_KEY = 4321
ZIPF_S = 1.2
N_HEADS = 4
LORA_ALPHA = 8.0


def _seqs(key, offsets, n, cfg):
    v, t = cfg["vocab"], cfg["seq_len"]
    logits = -ZIPF_S * jnp.log(jnp.arange(1, v + 1, dtype=jnp.float32))
    k0, ks = jax.random.split(key)
    first = jax.random.categorical(k0, logits, shape=(n,))
    steps = jax.random.categorical(ks, logits, shape=(t, n))

    def nxt(tok, step):
        tok = (offsets[tok] + step) % v
        return tok, tok

    _, rest = jax.lax.scan(nxt, first, steps)
    seq = jnp.concatenate([first[None], rest], axis=0).T.astype(jnp.int32)   # (n, t + 1)
    return seq[:, :-1], seq[:, 1:]


def make_data(key_train, key_test, cfg):
    """``(x, y, x_test, y_test)``: (n, seq_len) int32 tokens and their next
    tokens."""
    offsets = jax.random.randint(jax.random.PRNGKey(TEMPLATE_KEY), (cfg["vocab"],), 0,
                                 cfg["vocab"])
    x, y = _seqs(key_train, offsets, cfg["n_train"], cfg)
    x_test, y_test = _seqs(key_test, offsets, cfg["n_test"], cfg)
    return x, y, x_test, y_test


def init(key, cfg, dtype=jnp.float32):
    """The adapters: ``l<i>.{q,v}_a`` (d, r) normal / sqrt(d), ``l<i>.{q,v}_b``
    (r, d) zero."""
    d, r = cfg["d_model"], cfg["lora_rank"]
    out = {}
    for i in range(cfg["n_layers"]):
        for j, p in enumerate("qv"):
            a = jax.random.normal(jax.random.fold_in(key, 2 * i + j), (d, r)) / d ** 0.5
            out[f"l{i}.{p}_a"] = a.astype(dtype)
            out[f"l{i}.{p}_b"] = jnp.zeros((r, d), dtype)
    return out


def init_frozen(key, cfg, dtype=jnp.float32):
    """The base: normal weights scaled by 1 / sqrt(fan-in)."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    shapes = {"embed": (v, d), "head": (d, v)}
    for i in range(cfg["n_layers"]):
        shapes.update({f"l{i}.wq": (d, d), f"l{i}.wk": (d, d), f"l{i}.wv": (d, d),
                       f"l{i}.wo": (d, d), f"l{i}.w1": (d, f), f"l{i}.w2": (f, d)})
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        scale = 1.0 if name == "embed" else shape[0] ** -0.5
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape) * scale).astype(dtype)
    return out


def _logits(params, frozen, tokens, precision, dtype):
    p = {k: v.astype(dtype) for k, v in params.items()}
    w = {k: v.astype(dtype) for k, v in frozen.items()}
    b, t = tokens.shape
    d = w["embed"].shape[1]
    dh = d // N_HEADS

    def mm(x, m):
        return jnp.dot(x, m, precision=precision, preferred_element_type=dtype)

    def norm(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6).astype(dtype)

    x = w["embed"][tokens]
    mask = jnp.tril(jnp.ones((t, t), bool))
    for i in range(len([k for k in frozen if k.endswith(".wq")])):
        h = norm(x)
        scale = LORA_ALPHA / p[f"l{i}.q_a"].shape[1]
        q = mm(h, w[f"l{i}.wq"]) + scale * mm(mm(h, p[f"l{i}.q_a"]), p[f"l{i}.q_b"])
        k = mm(h, w[f"l{i}.wk"])
        v = mm(h, w[f"l{i}.wv"]) + scale * mm(mm(h, p[f"l{i}.v_a"]), p[f"l{i}.v_b"])
        q, k, v = (z.reshape(b, t, N_HEADS, dh) for z in (q, k, v))
        s = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=precision,
                       preferred_element_type=dtype) / dh ** 0.5
        a = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhe->bqhe", a, v, precision=precision,
                       preferred_element_type=dtype).reshape(b, t, d)
        x = x + mm(o, w[f"l{i}.wo"])
        x = x + mm(jax.nn.gelu(mm(norm(x), w[f"l{i}.w1"])), w[f"l{i}.w2"])
    return mm(norm(x), w["head"])


def loss(params, frozen, xs, ys, *, precision, dtype):
    """Mean next-token cross-entropy over every position of ``xs`` (B, T)."""
    logp = jax.nn.log_softmax(_logits(params, frozen, xs, precision, dtype)
                              .astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ys[..., None], axis=-1))


def accuracy(params, frozen, xs, ys, *, precision, dtype):
    """Share of positions whose most likely next token is the label."""
    logits = _logits(params, frozen, xs, precision, dtype).astype(jnp.float32)
    return jnp.mean(jnp.argmax(logits, -1) == ys)


def forward_flops(cfg) -> int:
    """FLOPs (2 per MAC) of one sequence's forward pass: per block the four
    projections, the two adapters (down and up), the scores and the
    weighted values over all T x T pairs, and the MLP; then the head. Norms,
    softmax and GELU are not counted."""
    t, d, f, r, v = (cfg[k] for k in ("seq_len", "d_model", "d_ff", "lora_rank", "vocab"))
    block = 8 * t * d * d + 8 * t * d * r + 4 * t * t * d + 4 * t * d * f
    return cfg["n_layers"] * block + 2 * t * d * v


def train_flops(cfg) -> int:
    """FLOPs of one sequence's forward and backward pass, where only the
    adapters train. The backward pass takes the input gradient of every
    product that lies above the first adapter (the head, every block but
    the first whole: projections 8Td^2, attention 8T^2d, MLP 4Tdf, adapters
    8Tdr), and the adapters' weight gradients (4Tdr each, 16Tdr a block
    with the input gradients). In the first
    block nothing below needs a gradient: it takes the MLP's and the output
    projection's input gradients, the attention's except the keys' (6T^2d),
    and per adapter the up-projection's input gradient and both weight
    gradients (6Tdr)."""
    t, d, f, r, v = (cfg[k] for k in ("seq_len", "d_model", "d_ff", "lora_rank", "vocab"))
    upper = 8 * t * d * d + 8 * t * t * d + 4 * t * d * f + 16 * t * d * r
    first = 2 * t * d * d + 6 * t * t * d + 4 * t * d * f + 12 * t * d * r
    return forward_flops(cfg) + 2 * t * d * v + (cfg["n_layers"] - 1) * upper + first
