"""Torch checkpoint → Flax variables conversion.

The reference loads torchvision-format pretrained weights
(`/root/reference/distribuuuu/models/utils.py:1-4`, URLs `resnet.py:23-33`,
DenseNet legacy-key remap `densenet.py:266-282`) and its own training
checkpoints are torch ``state_dict``s (`utils.py:374-380`). This module maps
those trees onto this framework's parameter layout so users migrating from
the reference keep their weights:

- conv ``[O, I, kh, kw]`` → HWIO kernels; BN weight/bias → scale/bias and
  running_mean/var → batch_stats; fc weight transposed.
- reference/torchvision ResNet naming (``layer1.0.conv1`` …) → our
  ``layer1_0/conv1`` modules, incl. ``downsample.{0,1}`` → ``ds_conv/ds_bn``.
- DenseNet ``features.denseblock{B}.denselayer{L}.*`` → ``block{B}_layer{L}``,
  transitions and the pre-1.0 dotted legacy names (``norm.1`` …) the
  reference also remaps.
- BoTNet: the reference builds botnet50 as a bare ``nn.Sequential``
  (`botnet.py:283-289`) so its checkpoints use numeric keys — ``0``=conv1,
  ``1``=bn1, ``4/5/6``=layer1-3, ``7.net.{i}``=BoTBlocks, ``10``=fc; mapped
  onto our named modules, incl. the MHSA qkv convs and rel-pos tables.
  ``pretrained=True`` semantics (resnet50 trunk warm-start, `botnet.py:280`)
  are provided by :func:`botnet50_trunk_from_resnet50`.
- EfficientNet-B0 / RegNetX/Y: the reference gets these from **timm**
  (`trainer.py:124-128`), so reference-trained checkpoints carry timm module
  naming (``conv_stem``/``blocks.{s}.{b}``; ``s{k}.b{j}.conv{n}.conv`` …);
  both are mapped here (timm ≥0.5 naming).

Checkpoints saved by the *reference trainer* wrap the model dict under
``state_dict`` with a possible ``module.`` DDP prefix (`utils.py:360-363`) —
both are stripped.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Mapping

import numpy as np


def _to_np(t) -> np.ndarray:
    try:
        return t.detach().cpu().numpy()
    except AttributeError:
        return np.asarray(t)


def _unwrap(state_dict: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    if "state_dict" in state_dict and isinstance(state_dict["state_dict"], Mapping):
        state_dict = state_dict["state_dict"]
    out = {}
    for k, v in state_dict.items():
        out[k.removeprefix("module.")] = _to_np(v)
    return out


def _conv_kernel(w: np.ndarray) -> np.ndarray:
    """[O, I/g, kh, kw] → [kh, kw, I/g, O] (flax HWIO)."""
    return np.transpose(w, (2, 3, 1, 0))


def _set(tree: dict, path: list[str], value: np.ndarray) -> None:
    node = tree
    for p in path[:-1]:
        node = node.setdefault(p, {})
    node[path[-1]] = value


_DENSENET_LEGACY = re.compile(
    r"^(.*denselayer\d+\.(?:norm|relu|conv))\.([12])\.(.*)$"
)


def _remap_densenet_legacy(key: str) -> str:
    """`norm.1.weight` → `norm1.weight` (reference `densenet.py:266-282`)."""
    m = _DENSENET_LEGACY.match(key)
    if m:
        return f"{m.group(1)}{m.group(2)}.{m.group(3)}"
    return key


def _module_path(torch_key: str, arch: str) -> tuple[list[str] | None, str]:
    """Map a torch module path (sans param name) to our module path.

    Returns (path-list, param-kind) where kind ∈ {conv, bn_affine, bn_stats,
    linear_w, linear_b, skip}.
    """
    parts = torch_key.split(".")
    name = parts[-1]
    mod = parts[:-1]

    if name in ("running_mean", "running_var"):
        kind = "bn_stats"
    elif name == "num_batches_tracked":
        return None, "skip"
    elif name in ("weight", "bias"):
        kind = None  # decided by module type below
    else:
        return None, "skip"

    if arch.startswith("densenet"):
        mod = [p for p in mod if p != "features"]
        mapped = []
        for p in mod:
            if p.startswith("denseblock"):
                mapped.append(f"block{p.removeprefix('denseblock')}")
            elif p.startswith("denselayer"):
                mapped[-1] = mapped[-1] + f"_layer{p.removeprefix('denselayer')}"
            elif p.startswith("transition"):
                mapped.append(f"trans{p.removeprefix('transition')}")
            else:
                mapped.append(p)
        # trans{B}.norm → trans{B}_norm; trans{B}.conv → trans{B}_conv
        out = []
        for p in mapped:
            if out and out[-1].startswith("trans") and p in ("norm", "conv"):
                out[-1] = out[-1] + "_" + p
            else:
                out.append(p)
        mod = out
    else:  # resnet family naming
        mapped = []
        i = 0
        while i < len(mod):
            p = mod[i]
            if re.fullmatch(r"layer\d+", p) and i + 1 < len(mod):
                mapped.append(f"{p}_{mod[i + 1]}")
                i += 2
            elif p == "downsample":
                # downsample.0 → ds_conv, downsample.1 → ds_bn
                sub = mod[i + 1]
                mapped.append("ds_conv" if sub == "0" else "ds_bn")
                i += 2
            else:
                mapped.append(p)
                i += 1
        mod = mapped

    leaf = mod[-1] if mod else ""
    is_bn = leaf.startswith(("bn", "norm")) or leaf.endswith(("bn", "norm")) or leaf in ("ds_bn",)
    is_linear = leaf in ("fc", "classifier")
    if kind is None:
        if is_linear:
            kind = "linear_w" if name == "weight" else "linear_b"
        elif is_bn:
            kind = "bn_affine"
        else:
            kind = "conv"
    return mod, kind


def _emit(params, batch_stats, path, torch_name, value, kind) -> None:
    """Route one torch tensor into the params/batch_stats trees.

    kind: ``conv`` (transpose OIHW→HWIO, bias kept as-is when present), ``bn``
    (affine → scale/bias, stats → mean/var), ``linear`` (transpose), ``raw``
    (copy as-is; ``path`` already includes the leaf name).
    """
    if kind == "conv":
        if torch_name == "weight":
            _set(params, path + ["kernel"], _conv_kernel(value))
        elif torch_name == "bias":
            _set(params, path + ["bias"], value)
    elif kind == "bn":
        if torch_name == "weight":
            _set(params, path + ["scale"], value)
        elif torch_name == "bias":
            _set(params, path + ["bias"], value)
        elif torch_name == "running_mean":
            _set(batch_stats, path + ["mean"], value)
        elif torch_name == "running_var":
            _set(batch_stats, path + ["var"], value)
    elif kind == "linear":
        if torch_name == "weight":
            _set(params, path + ["kernel"], value.T)
        else:
            _set(params, path + ["bias"], value)
    elif kind == "raw":
        _set(params, path, value)


# reference botnet50 Sequential slots (`botnet.py:283-289`): 0=conv1 1=bn1
# 2=relu 3=maxpool 4..6=layer1..3 7=BoTStack 8=avgpool 9=flatten 10=fc
def _convert_botnet50(sd: Dict[str, np.ndarray]) -> dict:
    params: dict = {}
    batch_stats: dict = {}
    # BoTBlock.net Sequential slots (`botnet.py:132-149`): 0=conv_in 1=bn_in
    # 2=act 3=MHSA 4=avgpool/identity 5=bn_mid 6=act 7=conv_out 8=bn_out
    net_slots = {
        "0": ("conv_in", "conv"),
        "1": ("bn_in", "bn"),
        "5": ("bn_mid", "bn"),
        "7": ("conv_out", "conv"),
        "8": ("bn_out", "bn"),
    }
    for key, value in sd.items():
        parts = key.split(".")
        name = parts[-1]
        if name == "num_batches_tracked":
            continue
        top = parts[0]
        if top == "0":
            _emit(params, batch_stats, ["conv1"], name, value, "conv")
        elif top == "1":
            _emit(params, batch_stats, ["bn1"], name, value, "bn")
        elif top in ("4", "5", "6"):
            block = [f"layer{int(top) - 3}_{parts[1]}"]
            inner = parts[2]
            if inner == "downsample":
                mod, kind = ("ds_conv", "conv") if parts[3] == "0" else ("ds_bn", "bn")
            else:
                mod, kind = inner, ("bn" if inner.startswith("bn") else "conv")
            _emit(params, batch_stats, block + [mod], name, value, kind)
        elif top == "7":  # BoTStack: 7.net.{i}.(shortcut|net).…
            block = [f"bot_{parts[2]}"]
            if parts[3] == "shortcut":
                mod, kind = ("sc_conv", "conv") if parts[4] == "0" else ("sc_bn", "bn")
                _emit(params, batch_stats, block + [mod], name, value, kind)
            else:
                slot = parts[4]
                if slot == "3":  # MHSA
                    sub = parts[5]
                    if sub in ("to_qk", "to_v"):
                        _emit(params, batch_stats, block + ["mhsa", sub], name, value, "conv")
                    else:  # pos_emb.{rel_height,rel_width,height,width}
                        _emit(
                            params, batch_stats,
                            block + ["mhsa", "pos_emb", parts[6]], name, value, "raw",
                        )
                else:
                    mod, kind = net_slots[slot]
                    _emit(params, batch_stats, block + [mod], name, value, kind)
        elif top == "10":
            _emit(params, batch_stats, ["fc"], name, value, "linear")
    return {"params": params, "batch_stats": batch_stats}


def botnet50_trunk_from_resnet50(state_dict: Mapping[str, Any]) -> dict:
    """Reference ``botnet50(pretrained=True)`` semantics (`botnet.py:275-290`):
    the pretrained **resnet50 trunk** (conv1/bn1/layer1-3) is reused and the
    BoTStack + classifier start fresh. Takes a torchvision/reference resnet50
    state_dict and returns the *partial* converted tree (trunk modules only);
    merge over freshly-initialized botnet50 variables with
    :func:`merge_pretrained`."""
    sd = _unwrap(state_dict)
    trunk = {
        k: v for k, v in sd.items()
        if k.split(".")[0] in ("conv1", "bn1", "layer1", "layer2", "layer3")
    }
    if not trunk:
        raise ValueError(
            "state_dict has no resnet50 trunk keys (conv1/bn1/layer1-3) — "
            "expected a torchvision/reference resnet50 checkpoint, got keys like "
            f"{sorted(sd)[:3]}"
        )
    # trunk module names are identical between our resnet50 and botnet50
    return convert_state_dict(trunk, "resnet50")


def merge_pretrained(variables: Mapping, partial: Mapping) -> dict:
    """Deep-merge a (possibly partial) converted tree over init variables."""
    out = dict(variables)
    for k, v in partial.items():
        if k in out and isinstance(out[k], Mapping) and isinstance(v, Mapping):
            out[k] = merge_pretrained(out[k], v)
        else:
            out[k] = v
    return out


# timm efficientnet_b0 block-module naming → ours. Stage 0 is timm's
# DepthwiseSeparableConv (no expansion); stages 1-6 are InvertedResidual.
_EFFNET_DS = {
    "conv_dw": ("dw_conv", "conv"),
    "bn1": ("dw_bn", "bn"),
    "conv_pw": ("project_conv", "conv"),
    "bn2": ("project_bn", "bn"),
}
_EFFNET_IR = {
    "conv_pw": ("expand_conv", "conv"),
    "bn1": ("expand_bn", "bn"),
    "conv_dw": ("dw_conv", "conv"),
    "bn2": ("dw_bn", "bn"),
    "conv_pwl": ("project_conv", "conv"),
    "bn3": ("project_bn", "bn"),
}


def _convert_efficientnet(sd: Dict[str, np.ndarray]) -> dict:
    params: dict = {}
    batch_stats: dict = {}
    for key, value in sd.items():
        parts = key.split(".")
        name = parts[-1]
        if name == "num_batches_tracked":
            continue
        top = parts[0]
        if top == "conv_stem":
            _emit(params, batch_stats, ["stem_conv"], name, value, "conv")
        elif top == "bn1":
            _emit(params, batch_stats, ["stem_bn"], name, value, "bn")
        elif top == "conv_head":
            _emit(params, batch_stats, ["head_conv"], name, value, "conv")
        elif top == "bn2":
            _emit(params, batch_stats, ["head_bn"], name, value, "bn")
        elif top == "classifier":
            _emit(params, batch_stats, ["classifier"], name, value, "linear")
        elif top == "blocks":
            si, bi = int(parts[1]), int(parts[2])
            block = [f"stage{si + 1}_block{bi + 1}"]
            mod = parts[3]
            if mod == "se":
                sub = "reduce" if parts[4] == "conv_reduce" else "expand"
                _emit(params, batch_stats, block + ["se", sub], name, value, "conv")
            else:
                tgt, kind = (_EFFNET_DS if si == 0 else _EFFNET_IR)[mod]
                _emit(params, batch_stats, block + [tgt], name, value, kind)
    return {"params": params, "batch_stats": batch_stats}


def _convert_regnet(sd: Dict[str, np.ndarray]) -> dict:
    """timm regnet naming: ``stem.conv/bn``, ``s{k}.b{j}.conv{n}.{conv,bn}``,
    ``se.fc{1,2}``, ``downsample.{conv,bn}``, ``head.fc``."""
    params: dict = {}
    batch_stats: dict = {}
    for key, value in sd.items():
        parts = key.split(".")
        name = parts[-1]
        if name == "num_batches_tracked":
            continue
        top = parts[0]
        if top == "stem":
            mod, kind = ("stem_conv", "conv") if parts[1] == "conv" else ("stem_bn", "bn")
            _emit(params, batch_stats, [mod], name, value, kind)
        elif top == "head":
            _emit(params, batch_stats, ["head_fc"], name, value, "linear")
        elif re.fullmatch(r"s\d+", top):
            stage, bi = int(top[1:]), int(parts[1].removeprefix("b"))
            block = [f"stage{stage}_block{bi}"]
            mod = parts[2]
            if mod in ("conv1", "conv2", "conv3"):
                n = mod[-1]
                tgt, kind = (mod, "conv") if parts[3] == "conv" else (f"bn{n}", "bn")
                _emit(params, batch_stats, block + [tgt], name, value, kind)
            elif mod == "se":
                sub = "reduce" if parts[3] == "fc1" else "expand"
                _emit(params, batch_stats, block + ["se", sub], name, value, "conv")
            elif mod == "downsample":
                tgt, kind = ("sc_conv", "conv") if parts[3] == "conv" else ("sc_bn", "bn")
                _emit(params, batch_stats, block + [tgt], name, value, kind)
    return {"params": params, "batch_stats": batch_stats}


def _convert_vit(sd: Dict[str, np.ndarray]) -> dict:
    """ViT (beyond-ref family, `models/vit.py`). Handles both public schemas:

    - torchvision ``vit_b_16``: ``conv_proj``, ``class_token``,
      ``encoder.pos_embedding``,
      ``encoder.layers.encoder_layer_{i}.{ln_1,self_attention,ln_2,mlp.linear_{1,2}}``
      (older releases name the MLP ``mlp.{0,3}``), ``encoder.ln``,
      ``heads.head``;
    - timm ``vit_*_patch16_224``: ``patch_embed.proj``, ``cls_token``,
      ``pos_embed``, ``blocks.{i}.{norm1,attn.{qkv,proj},norm2,mlp.fc{1,2}}``,
      ``norm``, ``head``.

    torch MHA packs in_proj as [3D, D] q/k/v-major then head-major — exactly
    the packing ``MultiHeadSelfAttention``'s reshape (b, l, 3, H, hd) reads,
    so the kernel is a plain transpose. timm's separate ``attn.qkv`` Linear
    uses the same packing.

    Keys that match no mapping **raise** (mirroring `verify_against_model`'s
    flax-side loudness): a qk_norm/head_dist variant checkpoint, a typo'd
    key, or a schema this table has never seen must fail the conversion with
    the full list of strays — silently dropping them would hand back a model
    that loads, runs, and scores garbage.
    """
    params: dict = {}

    def ln(path, name, value):
        _set(params, path + ["scale" if name == "weight" else "bias"], value)

    def linear(path, name, value):
        _set(params, path + ["kernel" if name == "weight" else "bias"],
             value.T if name == "weight" else value)

    def one(key: str, value) -> bool:
        """Emit one state_dict entry; False = no mapping covers it."""
        parts = key.split(".")
        name = parts[-1]
        top = parts[0]
        if top == "conv_proj" or (top == "patch_embed" and len(parts) > 2 and parts[1] == "proj"):
            if name not in ("weight", "bias"):
                return False
            if name == "weight":
                _set(params, ["patch_embed", "kernel"], _conv_kernel(value))
            else:
                _set(params, ["patch_embed", "bias"], value)
        elif key in ("class_token", "cls_token"):
            _set(params, ["cls_token"], value)
        elif key in ("encoder.pos_embedding", "pos_embed"):
            _set(params, ["pos_embed"], value)
        elif key.startswith("encoder.ln.") or (top == "norm" and len(parts) == 2):
            ln(["ln_f"], name, value)
        elif key.startswith("heads.head.") or (top == "head" and len(parts) == 2):
            linear(["head"], name, value)
        elif top == "encoder" and len(parts) > 3 and parts[1] == "layers":
            try:
                i = int(parts[2].removeprefix("encoder_layer_"))
            except ValueError:  # non-index segment: report as a stray key,
                return False  # not an opaque int() traceback
            block, mod = [f"block{i}"], parts[3]
            if mod in ("ln_1", "ln_2"):
                ln(block + ["ln" + mod[-1]], name, value)
            elif mod == "self_attention":
                if name in ("in_proj_weight", "in_proj_bias"):
                    linear(block + ["attn", "qkv"],
                           "weight" if name.endswith("weight") else "bias", value)
                elif len(parts) > 4 and parts[4] == "out_proj":
                    linear(block + ["attn", "proj"], name, value)
                else:  # e.g. a qk-norm variant's extra attention params
                    return False
            elif mod == "mlp" and len(parts) > 4:
                fc = {"linear_1": "fc1", "linear_2": "fc2", "0": "fc1", "3": "fc2"}.get(parts[4])
                if fc is None:
                    return False
                linear(block + [fc], name, value)
            else:
                return False
        elif top == "blocks" and len(parts) > 3:
            try:
                i = int(parts[1])
            except ValueError:
                return False
            block, mod = [f"block{i}"], parts[2]
            if mod in ("norm1", "norm2"):
                ln(block + ["ln" + mod[-1]], name, value)
            elif mod == "attn":
                tgt = {"qkv": "qkv", "proj": "proj"}.get(parts[3])
                if tgt is None:  # timm qk_norm (attn.q_norm/k_norm), etc.
                    return False
                linear(block + ["attn", tgt], name, value)
            elif mod == "mlp":
                linear(block + [parts[3]], name, value)
            else:
                return False
        else:
            return False
        return True

    unmatched = [key for key, value in sd.items() if not one(key, value)]
    if unmatched:
        raise ValueError(
            f"ViT conversion: {len(unmatched)} torch state_dict key(s) match "
            f"no mapping and would be silently dropped: {sorted(unmatched)}. "
            f"This usually means a model variant beyond the supported "
            f"torchvision/timm schemas (qk_norm, distilled head, ...) or a "
            f"typo'd key in a hand-edited checkpoint."
        )
    return {"params": params, "batch_stats": {}}


def convert_state_dict(state_dict: Mapping[str, Any], arch: str) -> dict:
    """torch state_dict → ``{"params": ..., "batch_stats": ...}`` numpy trees."""
    sd = _unwrap(state_dict)
    if arch.startswith("mae_"):
        raise ValueError(
            f"{arch} has no torch counterpart to convert from: MAE "
            "pretraining (models/mae.py) is a from-scratch workload — load "
            "dtpu checkpoints directly (MODEL.WEIGHTS)"
        )
    if arch == "botnet50":
        return _convert_botnet50(sd)
    if arch.startswith("vit"):
        return _convert_vit(sd)
    if arch.startswith("efficientnet"):
        return _convert_efficientnet(sd)
    if arch.startswith("regnet"):
        return _convert_regnet(sd)
    params: dict = {}
    batch_stats: dict = {}
    for key, value in sd.items():
        if arch.startswith("densenet"):
            key = _remap_densenet_legacy(key)
        mod, kind = _module_path(key, arch)
        if kind == "skip":
            continue
        name = key.split(".")[-1]
        if kind == "conv":
            _set(params, mod + ["kernel"], _conv_kernel(value))
        elif kind == "bn_affine":
            _set(params, mod + ["scale" if name == "weight" else "bias"], value)
        elif kind == "bn_stats":
            _set(batch_stats, mod + ["mean" if name == "running_mean" else "var"], value)
        elif kind == "linear_w":
            _set(params, mod + ["kernel"], value.T)
        elif kind == "linear_b":
            _set(params, mod + ["bias"], value)
    return {"params": params, "batch_stats": batch_stats}


# ---------------------------------------------------------------------------
# Export: Flax variables → torch-layout state_dict (migration is two-way).
#
# The exact inverse of :func:`convert_state_dict` per family —
# ``convert_state_dict(export_state_dict(v, arch), arch) == v`` leaf-exact
# (pinned for every registered arch in tests/test_convert_all_archs.py), and
# the emitted key set loads into the corresponding torch/torchvision/timm
# module with `load_state_dict` (pinned against real torch modules in
# tests/test_convert.py). Values are numpy; wrap with torch.from_numpy and
# torch.save to hand weights back to a reference/torch user.
# ---------------------------------------------------------------------------

# leaves stored verbatim on both sides (botnet rel-pos tables & fmap dims)
_RAW_LEAVES = {"rel_height", "rel_width", "height", "width"}


def _inv_resnet(mod):
    parts = []
    for p in mod:
        m = re.fullmatch(r"(layer\d+)_(\d+)", p)
        if m:
            parts += [m.group(1), m.group(2)]
        elif p == "ds_conv":
            parts += ["downsample", "0"]
        elif p == "ds_bn":
            parts += ["downsample", "1"]
        else:
            parts.append(p)
    return ".".join(parts)


def _inv_densenet(mod):
    parts = []
    for p in mod:
        m = re.fullmatch(r"block(\d+)_layer(\d+)", p)
        t = re.fullmatch(r"trans(\d+)_(norm|conv)", p)
        if m:
            parts += [f"features.denseblock{m.group(1)}", f"denselayer{m.group(2)}"]
        elif t:
            parts.append(f"features.transition{t.group(1)}.{t.group(2)}")
        elif p in ("conv0", "norm0", "norm5"):
            parts.append(f"features.{p}")
        else:
            parts.append(p)
    return ".".join(parts)


_INV_BOT_SLOTS = {
    "sc_conv": "shortcut.0",
    "sc_bn": "shortcut.1",
    "conv_in": "net.0",
    "bn_in": "net.1",
    "bn_mid": "net.5",
    "conv_out": "net.7",
    "bn_out": "net.8",
}


def _inv_botnet(mod):
    head = mod[0]
    if head == "conv1":
        return "0"
    if head == "bn1":
        return "1"
    if head == "fc":
        return "10"
    m = re.fullmatch(r"layer(\d+)_(\d+)", head)
    if m:
        rest = _inv_resnet(mod[1:])
        return f"{int(m.group(1)) + 3}.{m.group(2)}" + (f".{rest}" if rest else "")
    b = re.fullmatch(r"bot_(\d+)", head)
    if not b:
        raise KeyError(f"unmapped botnet module path {mod}")
    prefix = f"7.net.{b.group(1)}"
    inner = mod[1]
    if inner == "mhsa":
        if mod[2] in ("to_qk", "to_v"):
            return f"{prefix}.net.3.{mod[2]}"
        return f"{prefix}.net.3.pos_emb"  # raw leaf name appended by caller
    return f"{prefix}.{_INV_BOT_SLOTS[inner]}"


_INV_EFF_DS = {"dw_conv": "conv_dw", "dw_bn": "bn1", "project_conv": "conv_pw", "project_bn": "bn2"}
_INV_EFF_IR = {
    "expand_conv": "conv_pw",
    "expand_bn": "bn1",
    "dw_conv": "conv_dw",
    "dw_bn": "bn2",
    "project_conv": "conv_pwl",
    "project_bn": "bn3",
}


def _inv_efficientnet(mod):
    head = mod[0]
    flat = {
        "stem_conv": "conv_stem",
        "stem_bn": "bn1",
        "head_conv": "conv_head",
        "head_bn": "bn2",
        "classifier": "classifier",
    }
    if head in flat:
        return flat[head]
    m = re.fullmatch(r"stage(\d+)_block(\d+)", head)
    if not m:
        raise KeyError(f"unmapped efficientnet module path {mod}")
    prefix = f"blocks.{int(m.group(1)) - 1}.{int(m.group(2)) - 1}"
    inner = mod[1]
    if inner == "se":
        return f"{prefix}.se.conv_{'reduce' if mod[2] == 'reduce' else 'expand'}"
    inv = _INV_EFF_DS if m.group(1) == "1" else _INV_EFF_IR
    return f"{prefix}.{inv[inner]}"


def _inv_regnet(mod):
    head = mod[0]
    if head == "stem_conv":
        return "stem.conv"
    if head == "stem_bn":
        return "stem.bn"
    if head == "head_fc":
        return "head.fc"
    m = re.fullmatch(r"stage(\d+)_block(\d+)", head)
    if not m:
        raise KeyError(f"unmapped regnet module path {mod}")
    prefix = f"s{m.group(1)}.b{m.group(2)}"
    inner = mod[1]
    if inner == "se":
        return f"{prefix}.se.fc{'1' if mod[2] == 'reduce' else '2'}"
    if inner == "sc_conv":
        return f"{prefix}.downsample.conv"
    if inner == "sc_bn":
        return f"{prefix}.downsample.bn"
    c = re.fullmatch(r"(conv|bn)(\d)", inner)
    if not c:
        raise KeyError(f"unmapped regnet module path {mod}")
    return f"{prefix}.conv{c.group(2)}.{'conv' if c.group(1) == 'conv' else 'bn'}"


def _family_inverse(arch):
    if arch == "botnet50":
        return _inv_botnet
    if arch.startswith("densenet"):
        return _inv_densenet
    if arch.startswith("efficientnet"):
        return _inv_efficientnet
    if arch.startswith("regnet"):
        return _inv_regnet
    return _inv_resnet


def _export_vit(variables) -> Dict[str, np.ndarray]:
    """ViT inverse (torchvision ``vit_b_16`` schema — the qkv/out_proj leaves
    are whole-key renames, so the prefix-join scheme doesn't apply)."""
    sd: Dict[str, np.ndarray] = {}
    for path, leaf in _flatten(variables.get("params", {})):
        val = np.asarray(leaf)
        mod, leaf_name = list(path[:-1]), path[-1]
        if not mod:
            sd["class_token" if leaf_name == "cls_token" else "encoder.pos_embedding"] = val
        elif mod[0] == "patch_embed":
            if leaf_name == "kernel":
                sd["conv_proj.weight"] = np.transpose(val, (3, 2, 0, 1))
            else:
                sd["conv_proj.bias"] = val
        elif mod[0] == "ln_f":
            sd[f"encoder.ln.{'weight' if leaf_name == 'scale' else 'bias'}"] = val
        elif mod[0] == "head":
            sd[f"heads.head.{'weight' if leaf_name == 'kernel' else 'bias'}"] = (
                val.T if leaf_name == "kernel" else val
            )
        else:
            i = int(mod[0].removeprefix("block"))
            p = f"encoder.layers.encoder_layer_{i}"
            if mod[1] in ("ln1", "ln2"):
                sd[f"{p}.ln_{mod[1][-1]}.{'weight' if leaf_name == 'scale' else 'bias'}"] = val
            elif mod[1] == "attn" and mod[2] == "qkv":
                sd[f"{p}.self_attention.in_proj_{'weight' if leaf_name == 'kernel' else 'bias'}"] = (
                    val.T if leaf_name == "kernel" else val
                )
            elif mod[1] == "attn":
                sd[f"{p}.self_attention.out_proj.{'weight' if leaf_name == 'kernel' else 'bias'}"] = (
                    val.T if leaf_name == "kernel" else val
                )
            else:  # fc1 / fc2
                sd[f"{p}.mlp.linear_{mod[1][-1]}.{'weight' if leaf_name == 'kernel' else 'bias'}"] = (
                    val.T if leaf_name == "kernel" else val
                )
    return sd


def export_state_dict(variables: Mapping, arch: str) -> Dict[str, np.ndarray]:
    """Flax ``{"params", "batch_stats"}`` → torch-layout state_dict.

    The counterpart of :func:`convert_state_dict`, so reference/torch users
    can take dtpu-trained weights *back* (the reference's checkpoints are
    torch state_dicts, `/root/reference/distribuuuu/utils.py:374-380`).
    Emits the same per-family naming `convert_state_dict` accepts:
    torchvision for resnet/densenet/vit, the reference's Sequential
    numbering for botnet50, timm for efficientnet/regnet. Values are numpy
    (OIHW convs, [out, in] linears, running stats); ``num_batches_tracked``
    buffers are not emitted — pass ``strict=False`` to ``load_state_dict``
    or backfill zeros if the target module carries them.
    """
    if arch.startswith("mae_"):
        raise ValueError(
            f"{arch} has no torch-layout schema to export to (no published "
            "torch counterpart); ship the dtpu checkpoint itself"
        )
    if arch.startswith("vit"):
        return _export_vit(variables)
    mod_inv = _family_inverse(arch)
    sd: Dict[str, np.ndarray] = {}
    for col in ("params", "batch_stats"):
        for path, leaf in _flatten(variables.get(col, {})):
            val = np.asarray(leaf)
            mod, leaf_name = list(path[:-1]), path[-1]
            prefix = mod_inv(mod)
            if leaf_name in _RAW_LEAVES:
                sd[f"{prefix}.{leaf_name}"] = val
            elif col == "batch_stats":
                sd[f"{prefix}.running_{'mean' if leaf_name == 'mean' else 'var'}"] = val
            elif leaf_name == "kernel":
                sd[f"{prefix}.weight"] = (
                    np.transpose(val, (3, 2, 0, 1)) if val.ndim == 4 else val.T
                )
            elif leaf_name == "scale":
                sd[f"{prefix}.weight"] = val
            else:
                if leaf_name != "bias":
                    raise KeyError(f"unmapped leaf {path} for {arch}")
                sd[f"{prefix}.bias"] = val
    return sd


def load_torch_file(path: str, *, unsafe: bool = False) -> Mapping[str, Any]:
    """Load a torch checkpoint with safe unpickling.

    ``weights_only=True`` loads torchvision/timm state_dicts and reference
    trainer checkpoints fine. Legacy pickles that need arbitrary-code
    unpickling require an explicit ``unsafe=True`` opt-in (checkpoints from
    untrusted sources can execute code on load otherwise).
    """
    import pickle

    import torch

    try:
        return torch.load(path, map_location="cpu", weights_only=True)
    except (pickle.UnpicklingError, RuntimeError) as e:
        if not unsafe:
            raise RuntimeError(
                f"{path} is not loadable with torch safe-unpickling "
                "(weights_only=True). If you trust this file, retry with "
                "--unsafe (load_torch_file(path, unsafe=True))."
            ) from e
        return torch.load(path, map_location="cpu", weights_only=False)


# ---------------------------------------------------------------------------
# Golden-logits fixtures (scripts/validate_pretrained.py --synthetic-init;
# the serving tests' correctness oracle, docs/SERVING.md)
# ---------------------------------------------------------------------------

def golden_inputs(n: int, size: int, seed: int = 0) -> np.ndarray:
    """The fixtures' fixed inputs: seeded standard-normal ``(n, s, s, 3)``
    float32 — post-normalization scale, like real batches after
    transforms.normalize. Deterministic across platforms (PCG64)."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, size, size, 3), dtype=np.float32)


def synthetic_variables(
    arch: str, init_seed: int, im_size: int, num_classes: int
) -> dict:
    """Deterministic seeded-init variables for ``arch`` as host numpy.

    The weights side of a *synthetic* golden fixture: `(arch, init_seed,
    im_size, num_classes)` determines the model for one JAX version and one
    ``jax_threefry_partitionable`` setting (the same seed draws other bits
    when either changes — the checked-in fixtures are regenerated with
    `scripts/validate_pretrained.py --synthetic-init` when the installed JAX
    does), so a CPU-sized fixture checked into the repo can be re-derived —
    and served — without torch, network, or large checked-in weight files.
    """
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.models import build_model

    model = build_model(arch, num_classes=num_classes, dtype=jnp.float32)
    variables = model.init(
        jax.random.PRNGKey(init_seed),
        jnp.zeros((1, im_size, im_size, 3), jnp.float32),
        train=False,
    )
    out = {k: jax.tree.map(np.asarray, dict(v)) for k, v in variables.items()}
    out.setdefault("batch_stats", {})
    return out


def golden_fixture(
    arch: str,
    *,
    init_seed: int,
    im_size: int,
    num_classes: int,
    n: int = 4,
    input_seed: int = 0,
) -> dict:
    """Compute a synthetic golden-logits fixture (JSON-ready dict).

    Provenance fields (arch/init_seed/im_size/num_classes/input_seed/n plus
    the sha256 of the raw input bytes) ride along so a checker can refuse a
    fixture that does not describe the run being checked — the same gate
    validate_pretrained.py applies to its torch goldens.
    """
    import hashlib

    import jax.numpy as jnp

    from distribuuuu_tpu.models import build_model

    variables = synthetic_variables(arch, init_seed, im_size, num_classes)
    x = golden_inputs(n, im_size, input_seed)
    model = build_model(arch, num_classes=num_classes, dtype=jnp.float32)
    logits = model.apply(
        {"params": variables["params"], "batch_stats": variables["batch_stats"]},
        jnp.asarray(x),
        train=False,
    )
    return {
        "arch": arch,
        "init_seed": int(init_seed),
        "im_size": int(im_size),
        "num_classes": int(num_classes),
        "input_seed": int(input_seed),
        "n": int(n),
        "input_sha256": hashlib.sha256(x.tobytes()).hexdigest(),
        "logits": np.asarray(logits, dtype=np.float32).tolist(),
    }


def verify_against_model(converted: dict, arch: str, num_classes: int = 1000) -> None:
    """Raise if the converted tree doesn't match the model's expected tree."""
    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.models import build_model

    model = build_model(arch, num_classes=num_classes)
    expected = jax.eval_shape(
        lambda k, x: model.init(k, x, train=False),
        jax.random.PRNGKey(0),
        jnp.zeros((1, 224, 224, 3), jnp.float32),
    )

    def compare(exp_tree, got_tree, which):
        exp_flat = {"/".join(map(str, k)): v for k, v in _flatten(exp_tree)}
        got_flat = {"/".join(map(str, k)): v for k, v in _flatten(got_tree)}
        missing = exp_flat.keys() - got_flat.keys()
        extra = got_flat.keys() - exp_flat.keys()
        if missing or extra:
            raise ValueError(
                f"{which} mismatch for {arch}: missing={sorted(missing)[:5]} "
                f"extra={sorted(extra)[:5]} (showing ≤5)"
            )
        for k, v in exp_flat.items():
            if tuple(v.shape) != tuple(got_flat[k].shape):
                raise ValueError(
                    f"{which}/{k}: shape {got_flat[k].shape} != expected {v.shape}"
                )

    compare(expected["params"], converted["params"], "params")
    compare(expected.get("batch_stats", {}), converted["batch_stats"], "batch_stats")


def _flatten(tree, prefix=()):
    out = []
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            out.extend(_flatten(v, prefix + (k,)))
    else:
        out.append((prefix, tree))
    return out
