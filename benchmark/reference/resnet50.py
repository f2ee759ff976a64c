"""Plain reference: ResNet-50 v1.5 (He et al. 2015; torchvision stride placement).

Forward in train mode, softmax cross-entropy and gradients in ``jax.numpy``
float32 at ``highest`` matmul precision. Imports nothing of the program; the
benchmark makes the weights here (``init``) and hands the same values to the
program through ``to_program``.

Departures from the published description, each on purpose:

- NHWC layout and HWIO kernels (a layout, not a different function).
- BatchNorm running variance uses the biased batch variance, as flax does
  (torch uses the unbiased one; at 256x7x7 rows the two differ by 8e-5).
- Every bottleneck is wrapped in ``jax.checkpoint`` so that the float32
  backward pass at batch 256 fits beside nothing else on a 16 GB chip. That
  changes memory, not values.
- ``precision`` other than ``"f32"`` is the *control* of the benchmark's
  ``correct`` (the same mathematics with matmul/conv operands rounded to a
  lower precision); it is never the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.precision import product

STAGES = (3, 4, 6, 3)
BN_EPS = 1e-5
BN_MOMENTUM = 0.9  # running = 0.9 * running + 0.1 * batch
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


# --------------------------------------------------------------------------
# parameter inventory
# --------------------------------------------------------------------------

def _blocks():
    """Yield (prefix, cin, planes, stride, downsample) for the 16 bottlenecks."""
    cin = 64
    for stage, n in enumerate(STAGES):
        planes = 64 * 2**stage
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            yield f"s{stage + 1}.b{i}", cin, planes, stride, (stride != 1 or cin != 4 * planes)
            cin = 4 * planes


def shapes(settings: dict) -> dict[str, tuple]:
    """Flat name -> shape of every trainable leaf (``settings``: the keys merged into the program's ``cfg``)."""
    num_classes = int(settings["MODEL"]["NUM_CLASSES"])
    out: dict[str, tuple] = {"stem.conv": (7, 7, 3, 64), "stem.bn.scale": (64,), "stem.bn.bias": (64,)}
    for p, cin, planes, _, ds in _blocks():
        out[f"{p}.conv1"] = (1, 1, cin, planes)
        out[f"{p}.conv2"] = (3, 3, planes, planes)
        out[f"{p}.conv3"] = (1, 1, planes, 4 * planes)
        for j, c in ((1, planes), (2, planes), (3, 4 * planes)):
            out[f"{p}.bn{j}.scale"] = (c,)
            out[f"{p}.bn{j}.bias"] = (c,)
        if ds:
            out[f"{p}.ds.conv"] = (1, 1, cin, 4 * planes)
            out[f"{p}.ds.bn.scale"] = (4 * planes,)
            out[f"{p}.ds.bn.bias"] = (4 * planes,)
    out["fc.w"] = (2048, num_classes)
    out["fc.b"] = (num_classes,)
    return out


def init(key, settings: dict) -> dict[str, jax.Array]:
    """Seeded weights: kaiming-normal fan-out convs, unit BN scale, zero
    biases, U(+-1/sqrt(fan_in)) classifier (the published initialisation)."""
    params = {}
    for i, (name, shape) in enumerate(shapes(settings).items()):
        k = jax.random.fold_in(key, i)
        if len(shape) == 4:
            fan_out = shape[0] * shape[1] * shape[3]
            params[name] = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_out) ** 0.5
        elif name == "fc.w":
            bound = shape[0] ** -0.5
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name.endswith("scale"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params


def init_stats(settings: dict) -> dict[str, jax.Array]:
    """Running statistics at their defined start: mean 0, variance 1."""
    stats = {}
    for name, shape in shapes(settings).items():
        if name.endswith(".scale"):
            bn = name[: -len(".scale")]
            stats[f"{bn}.mean"] = jnp.zeros(shape, jnp.float32)
            stats[f"{bn}.var"] = jnp.ones(shape, jnp.float32)
    return stats


# --------------------------------------------------------------------------
# the program's names for the same leaves (its flax tree)
# --------------------------------------------------------------------------

def _program_path(name: str) -> tuple[str, ...]:
    parts = name.split(".")
    if parts[0] == "stem":
        head: tuple[str, ...] = ()
        rest = parts[1:]
        rest[0] = {"conv": "conv1", "bn": "bn1"}[rest[0]]
    elif parts[0] == "fc":
        return ("fc", {"w": "kernel", "b": "bias"}[parts[1]])
    else:
        head = (f"layer{parts[0][1:]}_{parts[1][1:]}",)
        rest = parts[2:]
        if rest[0] == "ds":
            rest = [{"conv": "ds_conv", "bn": "ds_bn"}[rest[1]]] + rest[2:]
    if len(rest) == 1:  # a conv kernel
        rest = rest + ["kernel"]
    return head + tuple(rest)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, value in flat.items():
        node = tree
        path = _program_path(name)
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def to_program(params: dict, stats: dict) -> tuple[dict, dict]:
    """(params, batch_stats) in the program's tree layout."""
    return _nest(params), _nest(stats)


def from_program(tree: dict, names) -> dict:
    """Pick the flat ``names`` out of one of the program's trees."""
    out = {}
    for name in names:
        node = tree
        for part in _program_path(name):
            node = node[part]
        out[name] = node
    return out


def compare_leaves(flat: dict) -> dict:
    """The leaves whose norms are compared: every leaf as it is stored."""
    return flat


# --------------------------------------------------------------------------
# forward, loss
# --------------------------------------------------------------------------


def _conv(x, w, stride: int, pad: int, precision: str):
    conv = lambda x, w: lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=lax.Precision.HIGHEST,
    )
    return product(conv, x, w, precision)


def _bn(x, params, stats, new_stats, name: str):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    new_stats[f"{name}.mean"] = BN_MOMENTUM * stats[f"{name}.mean"] + (1 - BN_MOMENTUM) * mean
    new_stats[f"{name}.var"] = BN_MOMENTUM * stats[f"{name}.var"] + (1 - BN_MOMENTUM) * var
    y = (x - mean) * lax.rsqrt(var + BN_EPS)
    return y * params[f"{name}.scale"] + params[f"{name}.bias"]


def _bottleneck(x, params, stats, p: str, stride: int, ds: bool, precision: str):
    new_stats: dict = {}
    out = _conv(x, params[f"{p}.conv1"], 1, 0, precision)
    out = jax.nn.relu(_bn(out, params, stats, new_stats, f"{p}.bn1"))
    out = _conv(out, params[f"{p}.conv2"], stride, 1, precision)
    out = jax.nn.relu(_bn(out, params, stats, new_stats, f"{p}.bn2"))
    out = _conv(out, params[f"{p}.conv3"], 1, 0, precision)
    out = _bn(out, params, stats, new_stats, f"{p}.bn3")
    identity = x
    if ds:
        identity = _conv(x, params[f"{p}.ds.conv"], stride, 0, precision)
        identity = _bn(identity, params, stats, new_stats, f"{p}.ds.bn")
    return jax.nn.relu(out + identity), new_stats


def forward(params, stats, images_u8, precision: str = "f32"):
    """Train-mode forward. Returns (logits f32, new running statistics)."""
    x = images_u8.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
    new_stats: dict = {}
    x = _conv(x, params["stem.conv"], 2, 3, precision)
    x = jax.nn.relu(_bn(x, params, stats, new_stats, "stem.bn"))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)]
    )
    for p, _, _, stride, ds in _blocks():
        names = [n for n in params if n.startswith(p + ".")]
        snames = [n for n in stats if n.startswith(p + ".")]
        block = jax.checkpoint(
            lambda x, bp, bs, p=p, stride=stride, ds=ds: _bottleneck(x, bp, bs, p, stride, ds, precision)
        )
        x, block_stats = block(x, {n: params[n] for n in names}, {n: stats[n] for n in snames})
        new_stats.update(block_stats)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(x, params["fc.w"], precision=lax.Precision.HIGHEST) + params["fc.b"]
    return logits, new_stats


def loss_fn(params, stats, batch, precision: str = "f32"):
    """Mean softmax cross-entropy over the rows of ``batch``, a block of rows of the pool's dict
    (input kind ``image``). Returns (loss, new stats)."""
    logits, new_stats = forward(params, stats, batch["image"], precision)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(nll), new_stats
