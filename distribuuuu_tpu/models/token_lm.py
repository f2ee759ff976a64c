"""What the token-sequence models share: a stack of residual layers over token embeddings.

A family (`models/nemotron_h.py`, `models/qwen3_next.py`, `models/deepseek_v3.py`, `models/bailing_hybrid.py`,
`models/laguna.py`) gives its sizes, the
leaves of one layer of each kind of its pattern string, their initialisers
and the pure function that runs one layer on its own leaves; `TokenLM` is the
rest: token embedding in, the layers, a final RMSNorm and an untied head out.

The parameters are one flat dict (`param_shapes`). Where the pattern repeats
a unit (``EMEMEMEMEM*`` is five times ``EM``, then ``*``; ``GGGA`` three
times ``G``, then ``A``; ``DEEEE`` is ``D``, then four times ``E``), the
repeats' parameters are one leaf with the repeats leading (``U<j>_<leaf>
[repeats, ...]`` for the unit's layer ``j``) and run as one `lax.scan`, so
the compiler sees a unit once and no copy of a parameter is made to stack it.
The stack takes layers before the repeats as well as after them, each on its
own: ``L<i>_<leaf>`` for layer ``i`` of the pattern; beside them ``embed``,
``head``, ``norm_f``.

``__call__`` returns the final-normed hidden states and the routing
counters; `head_logits` maps hidden states to logits, so that a loss can take
the vocabulary in blocks of tokens (trainer._forward_loss_lm).

With ``remat`` every layer is one `jax.checkpoint` under a policy that keeps
the values the family names (``kept``), each in the dtype it has, and
computes everything else of the layer again in the backward pass. Without
``remat`` there is no checkpoint at all.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distribuuuu_tpu.obs.trace import step_scope
from distribuuuu_tpu.parallel.moe import BLOCK

F32 = jnp.float32
#: `jax.monitoring` event, one per layer traced under a family's policy (three a trace of ``EMEMEMEMEM*``:
#: the scanned unit's two layers once each, then the attention layer); the journal's ``counters`` carry it
REMAT_POLICY_EVENT = "remat_policy_layers"


def rms_norm(x, scale, eps: float, groups: int = 1):
    """``x / sqrt(mean(x²) + eps) · scale`` in float32, the mean over each of ``groups`` slices of the width."""
    x = x.astype(F32)
    grouped = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    grouped = grouped * lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
    return grouped.reshape(x.shape) * scale.astype(F32)


def mm(x, kernel):
    """``x @ kernel``, operands in ``x.dtype``, float32 out."""
    return jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=F32)


def mixer_proj(x, kernel):
    """`mm` of a product that carries the stream into or out of a mixer, under the step scope ``dtpu.mixer_proj``."""
    with step_scope("mixer_proj"):
        return mm(x, kernel)


def repeated_unit(pattern: str) -> tuple[int, int, int]:
    """``(first, unit length, repeats)`` of the stretch that `TokenLM` scans: the unit and count, at least two,
    that cover most of the pattern, the layers ``0 … first - 1`` before it (the earliest of equal stretches, the
    shortest unit of one stretch: ``DEEEE`` is ``D``, then ``E`` four times); ``(0, len, 1)`` where nothing repeats."""
    best, covered = (0, len(pattern), 1), 0
    for first in range(len(pattern)):
        for k in range(1, (len(pattern) - first) // 2 + 1):
            r = 1
            while pattern[first + r * k:first + (r + 1) * k] == pattern[first:first + k]:
                r += 1
            if r >= 2 and k * r > covered:
                best, covered = (first, k, r), k * r
    return best


def layer_prefixes(pattern: str) -> list[tuple[str, str, int]]:
    """``(prefix, kind, repeats)`` of every group of leaves, in the order the layers run: ``L<i>`` for each layer
    before the repeats (``repeats`` 0: no such axis), ``U<j>`` for layer ``j`` of the repeated unit (its leaves lead
    with the repeats), ``L<i>`` for each layer after them."""
    first, unit, repeats = repeated_unit(pattern)
    scanned = unit * repeats if repeats > 1 else 0
    single = lambda layers: [(f"L{i}", pattern[i], 0) for i in layers]
    return (single(range(first)) + [(f"U{j}", pattern[first + j], repeats) for j in range(unit if scanned else 0)]
            + single(range(first + scanned, len(pattern))))


def param_shapes(s, layer_shapes: Callable) -> dict[str, tuple]:
    """Flat name -> shape of every leaf of a model of sizes ``s`` (``pattern``, ``vocab``, ``dim``) whose
    layers hold what ``layer_shapes(kind, s)`` says."""
    out = {"embed": (s.vocab, s.dim)}
    for prefix, kind, repeats in layer_prefixes(s.pattern):
        lead = (repeats,) if repeats else ()
        out.update({f"{prefix}_{leaf}": lead + shape for leaf, shape in layer_shapes(kind, s).items()})
    out.update({"norm_f": (s.dim,), "head": (s.dim, s.vocab)})
    return out


def leaf_of(name: str) -> str:
    """A flat name's leaf: ``U1_in_proj`` and ``L10_in_proj`` are ``in_proj``; ``embed`` is itself."""
    prefix, _, leaf = name.partition("_")
    return leaf if prefix[0] in "LU" and prefix[1:].isdigit() else name


def sizes_from(cls, given: dict):
    """A family's ``Sizes`` from the keys of the config's ``LM`` section that it names; the section's other
    keys are another family's (and ``seq_len``, ``loss_block`` the batch's and the loss's)."""
    names = {f.name for f in cls.__dataclass_fields__.values()}
    return cls(**{k: v for k, v in given.items() if k in names})


class TokenLM(nn.Module):
    """A family subclasses this and sets, as class attributes: ``layer_shapes(kind, s)``,
    ``initializer(name, s)``, ``layer(kind, p, b_corr, h, s) -> (h, counts or None)``, ``final_norm(h, scale, eps)``,
    ``kept`` (the names its layer checkpoint keeps) and ``buffered`` (the kinds of layer whose router has a
    correction buffer ``[experts]``: a buffer of the checkpoint, not a parameter)."""

    sizes: Any
    dtype: Any = jnp.bfloat16
    remat: bool = False

    kept = ()
    buffered = ""

    def dummy_input(self, size: int):
        """What `trainer.create_train_state` initialises with: parameter shapes do not depend on the length."""
        del size
        return jnp.zeros((1, 8), jnp.int32)

    def setup(self):
        s = self.sizes
        self.p = {name: self.param(name, self.initializer(name, s), shape, F32)
                  for name, shape in param_shapes(s, self.layer_shapes).items()}
        # a buffer of the checkpoint, not a parameter: its update rule is the recipe's, not the model's
        self.b_corr = {
            prefix: self.variable("batch_stats", f"{prefix}_b_corr", jnp.zeros,
                                  ((repeats,) if repeats else ()) + (s.experts,), F32)
            for prefix, kind, repeats in layer_prefixes(s.pattern) if kind in self.buffered
        }

    def head_logits(self, hidden):
        """Float32 logits over the held vocabulary slice of final-normed hidden states ``[..., dim]``, under the
        step scope ``dtpu.lm_head``."""
        with step_scope("lm_head"):
            return mm(hidden, self.p["head"])

    def _leaves(self, prefix: str) -> tuple[dict, Any]:
        """The leaves of one prefix by their short names, and its router's buffer (None where it has none)."""
        leaves = {k[len(prefix) + 1:]: v for k, v in self.p.items() if k.startswith(prefix + "_")}
        return leaves, self.b_corr[prefix].value if prefix in self.b_corr else None

    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no running statistics
        s = self.sizes
        layer = type(self).layer
        one_layer = scanned_layer = layer
        if self.remat:
            def under_policy(**options):
                remat_layer = jax.checkpoint(layer, static_argnums=(0, 4),
                                             policy=jax.checkpoint_policies.save_only_these_names(*self.kept), **options)

                def counted(*args):
                    jax.monitoring.record_event(REMAT_POLICY_EVENT)  # at trace time: once a layer traced
                    return remat_layer(*args)

                return counted

            one_layer = under_policy()
            # `lax.scan` already keeps the compiler from merging the recomputation with the forward pass; the
            # barrier `prevent_cse` adds inside it cost 3.7 to 4.9 ms of a 280 ms step on the chip (PERF.md §5, PR 32)
            scanned_layer = under_policy(prevent_cse=False)

        h = self.p["embed"][tokens].astype(self.dtype)
        loads = []
        groups = layer_prefixes(s.pattern)
        unit = [(prefix, kind) for prefix, kind, repeats in groups if repeats]

        def one_unit(h, leaves_and_buffers):
            counts = []
            for (prefix, kind), (leaves, b_corr) in zip(unit, leaves_and_buffers):
                with jax.named_scope(prefix):
                    h, c = scanned_layer(kind, leaves, b_corr, h, s)
                counts += [] if c is None else [c]
            return h, counts

        for prefix, kind, repeats in groups:  # in the order the layers run: those before the repeats, the repeats, the rest
            if not repeats:
                with jax.named_scope(prefix):
                    h, c = one_layer(kind, *self._leaves(prefix), h, s)
                loads += [] if c is None else [c.astype(F32)[None]]
            elif prefix == unit[0][0]:
                h, counts = lax.scan(one_unit, h, [self._leaves(name) for name, _ in unit])
                loads += [c.astype(F32) for c in counts]  # each [repeats, held]
        hidden = self.final_norm(h, self.p["norm_f"], s.eps).astype(self.dtype)
        counters = {}
        if loads:
            loads = jnp.concatenate(loads)  # [expert layers, held]
            counters = {
                "moe_slots_here": jnp.sum(loads),
                "moe_rows_here": jnp.sum(jnp.ceil(loads / BLOCK) * BLOCK),  # what the rounds computed: the slots in whole blocks
                "moe_load_max_over_mean": jnp.max(jnp.max(loads, axis=1) / jnp.maximum(jnp.mean(loads, axis=1), 1.0)),
            }
        return hidden, counters
