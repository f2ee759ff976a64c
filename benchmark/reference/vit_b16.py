"""Plain reference: ViT-B/16 (Dosovitskiy et al. 2020), torchvision ``vit_b_16`` layout.

Conv patch embedding with bias, learned class token and position table,
12 pre-LN encoder blocks (packed-qkv attention with 12 heads, erf-GELU MLP of
3072), final LayerNorm (eps 1e-6), linear head on the class token. Forward,
softmax cross-entropy and gradients in ``jax.numpy`` float32 at ``highest``
matmul precision. Imports nothing of the program.

Departures, each on purpose:

- The head is drawn from N(0, 0.02) and not zeros. With a zero head every
  gradient but the head's is exactly zero at step 1 and nothing upstream of
  the head would be compared there. The benchmark owns the weights, so it
  chooses ones that make every layer count.
- Each encoder block is wrapped in ``jax.checkpoint`` so that the float32
  backward at batch 128 fits a 16 GB chip. Memory only, not values.
- ``precision`` other than ``"f32"`` is the control of ``correct``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product

PATCH, DIM, DEPTH, HEADS, MLP = 16, 768, 12, 12, 3072
LN_EPS = 1e-6
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
HI = lax.Precision.HIGHEST


def shapes(settings: dict) -> dict[str, tuple]:
    """Flat name -> shape of every trainable leaf (``settings``: the keys merged into the program's ``cfg``)."""
    num_classes = int(settings["MODEL"]["NUM_CLASSES"])
    tokens = (int(settings["TRAIN"]["IM_SIZE"]) // PATCH) ** 2 + 1
    out: dict[str, tuple] = {
        "patch.w": (PATCH, PATCH, 3, DIM), "patch.b": (DIM,),
        "cls": (1, 1, DIM), "pos": (1, tokens, DIM),
    }
    for i in range(DEPTH):
        p = f"blk{i}"
        out.update({
            f"{p}.ln1.scale": (DIM,), f"{p}.ln1.bias": (DIM,),
            f"{p}.qkv.w": (DIM, 3 * DIM), f"{p}.qkv.b": (3 * DIM,),
            f"{p}.proj.w": (DIM, DIM), f"{p}.proj.b": (DIM,),
            f"{p}.ln2.scale": (DIM,), f"{p}.ln2.bias": (DIM,),
            f"{p}.fc1.w": (DIM, MLP), f"{p}.fc1.b": (MLP,),
            f"{p}.fc2.w": (MLP, DIM), f"{p}.fc2.b": (DIM,),
        })
    out.update({"lnf.scale": (DIM,), "lnf.bias": (DIM,),
                "head.w": (DIM, num_classes), "head.b": (num_classes,)})
    return out


def init(key, settings: dict) -> dict[str, jax.Array]:
    """Seeded weights: truncated-normal(0.02) tables, xavier-uniform
    projections, unit LN scale, zero biases, N(0, 0.02) head (see above)."""
    params = {}
    for i, (name, shape) in enumerate(shapes(settings).items()):
        k = jax.random.fold_in(key, i)
        if name in ("patch.w", "cls", "pos"):
            params[name] = 0.02 * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        elif name == "head.w":
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith(".w"):
            bound = (6.0 / (shape[0] + shape[1])) ** 0.5
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name.endswith("scale"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params


def init_stats(settings: dict) -> dict:
    return {}


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

_TOP = {"patch.w": ("patch_embed", "kernel"), "patch.b": ("patch_embed", "bias"),
        "cls": ("cls_token",), "pos": ("pos_embed",),
        "lnf.scale": ("ln_f", "scale"), "lnf.bias": ("ln_f", "bias"),
        "head.w": ("head", "kernel"), "head.b": ("head", "bias")}
_LEAF = {"w": "kernel", "b": "bias", "scale": "scale", "bias": "bias"}


def _program_path(name: str) -> tuple[str, ...]:
    if name in _TOP:
        return _TOP[name]
    blk, mod, leaf = name.split(".")
    head = (f"block{blk[3:]}",)
    if mod in ("qkv", "proj"):
        head += ("attn",)
    return head + (mod, _LEAF[leaf])


def to_program(params: dict, stats: dict) -> tuple[dict, dict]:
    tree: dict = {}
    for name, value in params.items():
        node = tree
        path = _program_path(name)
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree, {}


def from_program(tree: dict, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for part in _program_path(name):
            node = node[part]
        out[name] = node
    return out


def compare_leaves(flat: dict) -> dict:
    """The leaves whose norms are compared: the packed qkv projection as its three parts.

    Query, key and value are three parameters stored as one. The key's bias has
    no gradient under softmax (it shifts every score of a row alike), so under
    an adaptive optimizer it moves by round-off alone; as a leaf of its own the
    comparison's rule on the reference's gradient leaves it out.
    """
    out = {}
    for name, value in flat.items():
        if name.endswith(".qkv.w") or name.endswith(".qkv.b"):
            stem, kind = name[: -len("qkv.w")], name[-1]
            for part, piece in zip("qkv", jnp.split(value, 3, axis=-1)):
                out[f"{stem}{part}.{kind}"] = piece
        else:
            out[name] = value
    return out


# --------------------------------------------------------------------------
# forward, loss
# --------------------------------------------------------------------------


def _mm(spec: str, a, b, precision: str):
    return product(lambda a, b: jnp.einsum(spec, a, b, precision=HI), a, b, precision)


def _ln(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * scale + bias


def _block(x, bp, p: str, precision: str):
    b, l, d = x.shape
    hd = d // HEADS
    h = _ln(x, bp[f"{p}.ln1.scale"], bp[f"{p}.ln1.bias"])
    qkv = _mm("bld,de->ble", h, bp[f"{p}.qkv.w"], precision) + bp[f"{p}.qkv.b"]
    qkv = qkv.reshape(b, l, 3, HEADS, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    s = _mm("bhqd,bhkd->bhqk", q, k, precision) * hd**-0.5
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bhkd->bhqd", w, v, precision).transpose(0, 2, 1, 3).reshape(b, l, d)
    x = x + _mm("bld,de->ble", o, bp[f"{p}.proj.w"], precision) + bp[f"{p}.proj.b"]
    h = _ln(x, bp[f"{p}.ln2.scale"], bp[f"{p}.ln2.bias"])
    h = _mm("bld,de->ble", h, bp[f"{p}.fc1.w"], precision) + bp[f"{p}.fc1.b"]
    h = jax.nn.gelu(h, approximate=False)
    return x + _mm("bld,de->ble", h, bp[f"{p}.fc2.w"], precision) + bp[f"{p}.fc2.b"]


def forward(params, stats, images_u8, precision: str = "f32"):
    x = images_u8.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
    patches = lambda x, w: lax.conv_general_dilated(
        x, w, window_strides=(PATCH, PATCH), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )
    x = product(patches, x, params["patch.w"], precision) + params["patch.b"]
    b = x.shape[0]
    x = x.reshape(b, -1, DIM)
    x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, DIM)), x], axis=1)
    x = x + params["pos"]
    for i in range(DEPTH):
        p = f"blk{i}"
        bp = {n: params[n] for n in params if n.startswith(p + ".")}
        x = jax.checkpoint(lambda x, bp, p=p: _block(x, bp, p, precision))(x, bp)
    x = _ln(x, params["lnf.scale"], params["lnf.bias"])
    logits = jnp.dot(x[:, 0], params["head.w"], precision=HI) + params["head.b"]
    return logits, {}


def loss_fn(params, stats, batch, precision: str = "f32"):
    """Mean softmax cross-entropy over the rows of ``batch``, a block of rows of the pool's dict
    (input kind ``image``). Returns (loss, new stats)."""
    logits, new_stats = forward(params, stats, batch["image"], precision)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(nll), new_stats
