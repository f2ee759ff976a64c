"""Request/step tracing: trace ids, typed ``span`` journal records, and the
train loop's phases on the profiler's clock.

A *trace* is one unit of work whose phases should add up to an explainable
wall time — one served request (queue-wait → pad → device execute → total)
or one train PRINT_FREQ window (data-wait → throttle → dispatch → fetch-wait
→ host, plus the checkpoint dispatch at epoch boundaries). Every phase lands as a
``span`` record keyed by the trace id, so ``obs summarize`` can reconstruct
the critical path of the slowest traces from the journal alone.

The train side has one vocabulary of phases, owned here and measured once at
each boundary where the work happens (docs/OBSERVABILITY.md "Tracing"):

- `phase` wraps a host boundary of the loop or the input thread
  (`HOST_PHASES`). It is a ``dtpu.<name>`` span in a profiler trace when one
  runs, and always a ``<name>_s`` wait counter in the journal.
- `step_scope` names what follows the gradient inside the jitted train step
  (`STEP_SCOPES`) in the executable's own metadata, so a device trace splits
  the step by phase. The model's forward and backward keep the module paths
  flax gives them; `MODEL_SCOPES` name the parts of a layer and of the head
  those paths cannot tell apart.

Propagation contract (docs/OBSERVABILITY.md "Tracing"):

- The serve client mints the id (`mint_trace_id`) and sends it as the
  ``x-dtpu-trace-id`` header; **retries reuse the same id**, so a request
  that survived a replica kill reads as one trace with several attempts.
- The frontend validates the header (`ensure_trace_id` mints one for
  header-less callers), threads it through the batcher to the engine
  dispatch, and echoes it back in the response.
- Train-side ids are minted per window by `Telemetry.window`
  (``train-<run>-g<gstep>``) — no propagation needed, the run is the trace
  scope.

Spans carry host-measured wall times only — tracing adds zero device syncs
(the execute span is timed around the engine call whose result fetch *is*
the response payload; train spans reuse the PRINT_FREQ boundary fetch).
"""

from __future__ import annotations

import re
import time
import uuid

import jax

from distribuuuu_tpu.obs import telemetry as _telemetry

#: HTTP header carrying the trace id end-to-end (client -> frontend).
TRACE_HEADER = "x-dtpu-trace-id"

# ids are log- and label-safe by construction; anything else is replaced
# (a hostile header must not be able to inject journal/Prometheus syntax)
_TRACE_ID_RE = re.compile(r"^[A-Za-z0-9._\-]{1,128}$")

#: span phases of one served request, in causal order
SERVE_PHASES = ("queue_wait", "pad", "execute", "total")
#: span phases of one train window / epoch boundary; the first five sum to
#: the window's wall (``host`` is the rest: bookkeeping, and time the loop
#: thread did not run)
TRAIN_PHASES = ("data_wait", "throttle", "dispatch", "fetch_wait", "host", "checkpoint")

#: every profiler span and executable scope of the program starts with this
SPAN_PREFIX = "dtpu."
#: host boundaries `phase` wraps -> the wait counter each adds its seconds to
#: (``checkpoint`` has its journal span instead, trainer.train_model)
HOST_PHASES = {
    "h2d_transfer": "h2d_transfer_s",  # dtpu-h2d-prefetch: to_device(batch)
    "data_wait": "data_wait_s",        # loop: blocked on the prefetch queue
    "throttle": "throttle_s",          # loop: the step's first launches (its key)
    "dispatch": "dispatch_s",          # loop: the train_step call alone
    "fetch_wait": "fetch_wait_s",      # loop: device_get at the window boundary
    "checkpoint": None,                # loop: epoch-end save dispatch
}
#: inside a model, what flax's module paths cannot tell apart, one scope a
#: kind of work and each put where that work is done (docs/OBSERVABILITY.md
#: "Tracing" has the table of who puts it and which metric reads it):
#: ``ssm_scan`` the scan proper of a state-space mixer (ops/ssm.py);
#: ``gdn_scan`` the recurrence proper of a delta-rule mixer (ops/gdn.py);
#: ``latent_attn`` latent attention's core, put round
#: `ops.attention.latent_causal_attention` by models/deepseek_v3.py;
#: ``causal_attn`` every family's causal core, the kernel pair or XLA's blocks
#: and the layout copies around them (`ops.attention.causal_attention`; inside
#: ``latent_attn`` there); ``mixer_proj`` the products that carry the stream
#: into and out of a mixer (`models.token_lm.mixer_proj`); ``dense_ffn`` the
#: dense feed-forwards: shared experts, a leading dense layer; ``moe_route``
#: an expert layer's routing (scores, top-k, weights, sorting tokens to
#: experts) and ``moe_experts`` its grouped products over the experts held
#: (parallel/moe.py); ``lm_head`` the head's product in the loss's blocks
#: (`models.token_lm.TokenLM.head_logits`, inside ``loss``); ``short_conv``
#: a mixer's short causal convolution and its `silu` (ops/short_conv.py); ``kda_scan`` the recurrence proper of
#: a Kimi-delta-attention mixer (ops/kda.py); ``window_attn`` a sliding-window
#: causal core, inside ``causal_attn`` (`ops.attention.causal_attention` with a window)
MODEL_SCOPES = ("ssm_scan", "gdn_scan", "latent_attn", "causal_attn", "mixer_proj", "dense_ffn",
                "moe_route", "moe_experts", "lm_head", "short_conv", "kda_scan", "window_attn")
#: what follows the gradient inside the jitted train step, the loss outside
#: the module (trainer.make_train_step), and `MODEL_SCOPES`
STEP_SCOPES = ("grad_sync", "optimizer", "guard", "metrics", "loss") + MODEL_SCOPES


def mint_trace_id() -> str:
    """A fresh 16-hex-char trace id (collision-safe at journal scale)."""
    return uuid.uuid4().hex[:16]


def valid_trace_id(trace_id) -> bool:
    return isinstance(trace_id, str) and bool(_TRACE_ID_RE.match(trace_id))


def ensure_trace_id(trace_id) -> str:
    """The given id when well-formed, else a freshly minted one — malformed
    header values are *replaced*, never propagated into the journal."""
    return trace_id if valid_trace_id(trace_id) else mint_trace_id()


def span_fields(
    trace_id: str, phase: str, ms: float, **extra
) -> dict:
    """The fields of one ``span`` record (None-valued extras dropped, so
    call sites can pass optional context unconditionally)."""
    fields = {"trace_id": str(trace_id), "phase": str(phase), "ms": round(float(ms), 3)}
    fields.update({k: v for k, v in extra.items() if v is not None})
    return fields


def step_scope(name: str):
    """``jax.named_scope("dtpu.<name>")`` for one of `STEP_SCOPES`: metadata
    only — the step's arithmetic, fusions and outputs do not change."""
    if name not in STEP_SCOPES:
        raise ValueError(f"unknown step scope {name!r}: one of {STEP_SCOPES}")
    return jax.named_scope(SPAN_PREFIX + name)


class phase:
    """Context manager around one host boundary of the train loop or the
    input thread (`HOST_PHASES`): a ``dtpu.<name>`` profiler span carrying
    ``ids`` (``dispatch`` is a step annotation, so the profiler groups device
    work by ``step_num``), and on exit the elapsed seconds added to the
    phase's wait counter of the current run. Always on: with no trace running
    the annotation is a branch in the runtime, and the counter is two clock
    reads and one lock. ``seconds`` holds the elapsed time after exit.

    The clock stops when the wrapped call returns, deliberately without a
    device sync (the reasoning dtpu-lint's DT006 asks for): ``dispatch`` and
    ``h2d_transfer`` ARE the host's enqueue cost, whose device work may still
    be in flight, and ``fetch_wait`` wraps the fetch that is the sync.
    ``throttle`` wraps the first launches of a step, where the runtime blocks
    the host once the device's queue is full: with the device as the wall
    most of a step's time is spent there and not in ``fetch_wait``.
    """

    __slots__ = ("seconds", "_counter", "_annotation", "_t0")

    def __init__(self, name: str, **ids):
        self._counter = HOST_PHASES[name]
        annotation = (
            jax.profiler.StepTraceAnnotation
            if name == "dispatch"
            else jax.profiler.TraceAnnotation
        )
        self._annotation = annotation(SPAN_PREFIX + name, **ids)
        self.seconds = 0.0

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.monotonic() - self._t0
        self._annotation.__exit__(*exc)
        if self._counter is not None:
            _telemetry.current().add_wait(self._counter, self.seconds)
