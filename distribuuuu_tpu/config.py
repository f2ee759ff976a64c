"""Global config tree + CLI loading.

Mirrors the reference config surface (`/root/reference/distribuuuu/config.py:10-100`):
the same key tree, defaults, and precedence (defaults < --cfg YAML < trailing
``KEY VALUE`` opts, then freeze), so the shipped YAMLs and the documented
``train_net.py --cfg config/resnet50.yaml KEY VALUE ...`` UX work unchanged.

TPU-native additions (new sections; absent keys in old YAMLs simply keep defaults):

- ``MODEL.DTYPE``: compute dtype for the fwd/bwd pass ("bfloat16" rides the MXU
  at full rate; "float32" for exact-parity runs). Params/optimizer state/BN
  statistics always stay float32.
- ``MODEL.REMAT``: rematerialize (activation-checkpoint) each residual stage —
  the `jax.checkpoint` analog of the reference DenseNet's ``memory_efficient``
  (`densenet.py:81-108`), available for every model. The token models
  (`models/nemotron_h.py`, `models/qwen3_next.py`) checkpoint each layer under
  a policy: the values the family's ``KEPT`` names (the routing; nemotron_h's
  large projections' results) are stored, the rest of a layer is computed
  again in the backward pass.
- ``MESH.*``: device-mesh shape. DATA=-1 means "all visible devices" on the
  data axis (the reference is DP-only, `trainer.py:134`).
- ``CUDNN.*`` is kept for YAML compatibility and remapped: BENCHMARK is a no-op
  under XLA (autotuning is always on), DETERMINISTIC sets XLA deterministic ops.
"""

from __future__ import annotations

import argparse
import sys

from distribuuuu_tpu.cfgnode import CfgNode as CN

_C = CN()
cfg = _C

_C.MODEL = CN()
_C.MODEL.ARCH = "resnet18"
# Out-of-tree architectures: comma-separated module path(s) imported before
# MODEL.ARCH is resolved, so external packages can self-register archs with
# @register_model. The loud, explicit answer to the reference's silent timm
# fallback (`trainer.py:117-128`) — an import failure or unknown arch raises
# with the full story instead of quietly training a different model.
_C.MODEL.MODULE = ""
_C.MODEL.NUM_CLASSES = 1000
_C.MODEL.PRETRAINED = False
_C.MODEL.SYNCBN = False
_C.MODEL.WEIGHTS = None
_C.MODEL.DUMMY_INPUT = False
# TPU additions
_C.MODEL.DTYPE = "bfloat16"
_C.MODEL.REMAT = False
# Space-to-depth stem (resnet/botnet families): exact same math, MXU-shaped
# compute for the 7x7/2 3-channel stem conv. Checkpoint-compatible both ways.
_C.MODEL.STEM_S2D = False
# Fused conv-epilogue kernels (ops/epilogue.py, docs/PERFORMANCE.md
# "Epilogue fusion"): route each resnet-family conv→BN(→residual)→ReLU
# boundary through one VMEM-resident Pallas pass instead of XLA's separate
# fusions. Bitwise-identical output/grads to the unfused path (oracle-
# equality pinned in tests/test_epilogue.py; SyncBN/BN_DTYPE semantics
# unchanged — stats stay in flax code). Off until the kernels have an
# end-to-end verdict on the chip (PERF.md).
_C.MODEL.FUSED_EPILOGUE = False
# Sequence-parallel attention formulation once MESH.SEQ > 1 (parallel/seq.py,
# docs/PARALLELISM.md "The seq axis"): "ring" rotates K/V blocks over the seq
# axis (P-1 ppermute neighbor hops, any head count, O(L_local²) memory);
# "ulysses" reshards heads↔sequence with two all-to-alls and runs dense
# attention locally (needs heads % MESH.SEQ == 0). "none" (default) keeps the
# dense single-device attention — invalid with MESH.SEQ > 1 (tokens would be
# sharded with nothing stitching the attention contraction back together).
_C.MODEL.SEQ_ATTN = "none"
# Masked-autoencoder pretraining knobs (models/mae.py; active with
# TRAIN.TASK "mae"): fraction of patch tokens replaced by the learned mask
# token (SimMIM-style full-length masking — the token count stays static and
# seq-shardable), and the width of the pixel-decoder head.
_C.MODEL.MAE_MASK_RATIO = 0.25
_C.MODEL.MAE_DECODER_DIM = 512
# BatchNorm boundary dtype: what dtype BN *emits* between conv stages.
# Statistics are always computed in float32 and running stats/affine params
# always stored float32; "bfloat16" halves inter-stage HBM traffic (the
# MLPerf-era TPU recipe: +20% measured on resnet50/v5e),
# "float32" keeps full-precision boundaries. "auto" (default) tracks
# MODEL.DTYPE — bf16 training gets bf16 boundaries, f32 exact-parity runs
# stay f32 end-to-end.
_C.MODEL.BN_DTYPE = "auto"

_C.TRAIN = CN()
_C.TRAIN.BATCH_SIZE = 32  # per-device batch size, matching the reference's
#   per-GPU meaning (global batch = BATCH_SIZE * data-parallel size,
#   `README.md:198-201` linear-scaling table)
_C.TRAIN.IM_SIZE = 224
_C.TRAIN.DATASET = "./data/ILSVRC/"
_C.TRAIN.SPLIT = "train"
_C.TRAIN.AUTO_RESUME = True
_C.TRAIN.LOAD_OPT = True
_C.TRAIN.WORKERS = 4
_C.TRAIN.PIN_MEMORY = True  # kept for CLI compat; maps to device prefetch
_C.TRAIN.PRINT_FREQ = 30
_C.TRAIN.TOPK = 5
# Training task: "classify" (softmax-CE on labels — the reference's only
# task), "mae" (masked-autoencoder pixel reconstruction, models/mae.py:
# patch-masking in the input path, pixel MSE on masked patches; labels ride
# along unused; the large-L workload that exercises MESH.SEQ) or "lm"
# (next-token cross-entropy over rows of LM.SEQ_LEN + 1 token ids, batch key
# ``tokens``; the model is sized by the LM section).
_C.TRAIN.TASK = "classify"
# TPU additions
_C.TRAIN.PREFETCH = 2  # batches prefetched to device HBM ahead of compute
# synthetic samples per DUMMY_INPUT epoch (reference DummyDataset length,
# `utils.py:117`); raise for whole-loop throughput measurement runs
_C.TRAIN.DUMMY_EPOCH_SAMPLES = 1000
_C.TRAIN.LABEL_SMOOTH = 0.0
# Gradient accumulation: each optimizer step averages grads over ACCUM_STEPS
# micro-batches of BATCH_SIZE (effective global batch = BATCH_SIZE × devices
# × ACCUM_STEPS). The reference reaches large batches with more GPUs only
# (`README.md:178-192`); this reaches them on a fixed chip count.
_C.TRAIN.ACCUM_STEPS = 1
# Persistent XLA compilation cache (runtime/compile_cache.py): identical
# programs compile once per machine, not once per process per run — so a
# dtpu-agent supervised restart (or any relaunch) resumes without paying the
# full compile again. Cache hit/miss counts flow through the obs compile
# counters (/jax/compilation_cache/* in the journal's counters records).
_C.TRAIN.COMPILE_CACHE = True
# Cache directory ("" = the repo-local default next to the package checkout;
# set to a shared path, e.g. a persistent volume, for fleet-wide reuse).
# JAX_COMPILATION_CACHE_DIR in the environment beats both: the launcher
# places the cache, the program does not move it.
_C.TRAIN.COMPILE_CACHE_DIR = ""
# jax.profiler trace of a few steady-state steps (epoch 0) → OUT_DIR/profile.
# The reference has no profiler (SURVEY §5); this is the idiomatic upgrade.
_C.TRAIN.PROFILE = False
_C.TRAIN.PROFILE_START = 10  # first profiled step
_C.TRAIN.PROFILE_STEPS = 5

_C.TEST = CN()
_C.TEST.DATASET = "./data/ILSVRC/"
_C.TEST.SPLIT = "val"
_C.TEST.BATCH_SIZE = 200
_C.TEST.IM_SIZE = 256
_C.TEST.PRINT_FREQ = 10
# TPU addition: eval center-crop size. The reference hardcodes 224
# (`utils.py:166`); exposed here so small-resolution smokes can align train
# and eval shapes (position-embedding models require matching crops).
_C.TEST.CROP_SIZE = 224

_C.CUDNN = CN()
_C.CUDNN.BENCHMARK = True
_C.CUDNN.DETERMINISTIC = False

_C.OPTIM = CN()
# TPU addition: 'sgd' (reference-exact default), 'lamb' (layerwise-adaptive
# large-batch training — the standard recipe beyond the linear-scaling
# envelope the reference's SGD recipes stop at; BETA1/BETA2/EPS apply to it
# only) or 'adafactor' (Shazeer & Stern 2018: a factored second moment and no
# first, so the state is rows + columns and not two copies of the model — what
# a model takes whose parameters fill most of a chip).
_C.OPTIM.OPTIMIZER = "sgd"
_C.OPTIM.BETA1 = 0.9
_C.OPTIM.BETA2 = 0.999
_C.OPTIM.EPS = 1e-6
# Learning rate policy select from {'cos', 'steps'}
_C.OPTIM.MAX_EPOCH = 100
_C.OPTIM.LR_POLICY = "cos"
_C.OPTIM.BASE_LR = 0.2
_C.OPTIM.MIN_LR = 0.0
_C.OPTIM.STEPS = []
_C.OPTIM.LR_MULT = 0.1
_C.OPTIM.MOMENTUM = 0.9
_C.OPTIM.DAMPENING = 0.0
_C.OPTIM.NESTEROV = True
_C.OPTIM.WARMUP_FACTOR = 0.1
_C.OPTIM.WARMUP_EPOCHS = 5
_C.OPTIM.WEIGHT_DECAY = 5e-5

# Token-sequence model (TRAIN.TASK "lm"): the section reaches the arch's
# factory key by key in lower case; the factory lives in the module that
# MODEL.MODULE names and takes the keys its family's ``Sizes`` names
# (models/nemotron_h.py, models/qwen3_next.py, models/deepseek_v3.py; a key that two read is here once).
# Widths are the model's; the *_HELD counts, KV/group counts and VOCAB are
# what this chip holds of a layer shared over chips (all of it by default:
# the published counts of config/nemotron3_super.yaml's and
# config/qwen3_next.yaml's and config/kanana2_30b.yaml's sources are in those
# files' comments).
_C.LM = CN()
_C.LM.SEQ_LEN = 8192        # tokens a row (the batch ships SEQ_LEN + 1: inputs and labels are one leaf shifted)
_C.LM.VOCAB = 16384         # rows of embedding and head held; ids are drawn from 0 ... VOCAB - 1
# one letter a layer. nemotron_h: M Mamba-2, * attention, E latent experts; qwen3_next: G gated delta rule,
# A gated attention, each followed by its expert block; deepseek_v3: every layer latent attention, then D a dense
# feed-forward or E an expert block
_C.LM.PATTERN = "EMEMEMEMEM*"
_C.LM.LAYERS_TOTAL = 88     # depth of the whole model (scales the residual projections' init)
_C.LM.DIM = 4096
_C.LM.MAMBA_HEADS = 16
_C.LM.MAMBA_HEAD_DIM = 64
_C.LM.MAMBA_GROUPS = 1
_C.LM.SSM_STATE = 128
_C.LM.CONV_KERNEL = 4
_C.LM.CHUNK = 128
_C.LM.ATTN_HEADS = 4
_C.LM.KV_HEADS = 1
_C.LM.HEAD_DIM = 128
_C.LM.EXPERTS = 512         # the router's outputs
_C.LM.EXPERTS_HELD = 8      # experts EXPERT_FIRST ... EXPERT_FIRST + EXPERTS_HELD - 1 live here
_C.LM.EXPERT_FIRST = 0
_C.LM.TOP_K = 22
_C.LM.LATENT = 1024
_C.LM.EXPERT_WIDTH = 2688
_C.LM.SHARED_WIDTH = 5376
_C.LM.ROUTED_SCALE = 5.0
_C.LM.NORM_EPS = 1e-5
# what qwen3_next reads beside the keys above (DIM, CONV_KERNEL, CHUNK, the attention's and the experts')
_C.LM.LINEAR_KEY_HEADS = 16    # gated delta rule: key (and query) heads, each serving VALUE_HEADS / KEY_HEADS value heads
_C.LM.LINEAR_VALUE_HEADS = 32
_C.LM.LINEAR_KEY_DIM = 128
_C.LM.LINEAR_VALUE_DIM = 128
_C.LM.ROPE_SHARE = 0.25        # of a head's dimensions, the first, that the rotary embedding turns
_C.LM.ROPE_THETA = 1.0e7
# what deepseek_v3 reads beside the keys above (DIM, ATTN_HEADS, ROPE_THETA, ROUTED_SCALE, the experts')
_C.LM.KV_LATENT = 512          # latent attention: the normed latent that keys and values are expanded from
_C.LM.QK_NOPE_DIM = 128        # a head's query/key dimensions that carry no position ...
_C.LM.QK_ROPE_DIM = 64         # ... and its rotary ones; the key's are one head that all heads share
_C.LM.V_HEAD_DIM = 128
_C.LM.DENSE_WIDTH = 6144       # the gated feed-forward of a layer that has no experts
# tokens a block of the loss: float32 logits exist for one block at a time
_C.LM.LOSS_BLOCK = 2048

# Device mesh (TPU addition). The reference's only axis is data parallelism;
# axes are declared here so multi-axis meshes (see parallel/) slot in.
_C.MESH = CN()
_C.MESH.DATA = -1  # -1: all devices on the 'data' axis
# ZeRO-style parameter + optimizer-state sharding (parallel/fsdp.py,
# docs/PARALLELISM.md): >1 grows the training mesh to ('data', 'fsdp') and
# shards params/grads/optimizer state over the fsdp axis (all-gather on use,
# reduce-scatter grads, 1/N per-chip state). -1: every device not claimed by
# DATA (with DATA=-1 too, pure FSDP over the whole fleet). Composes with data
# parallelism: batches shard over both axes.
_C.MESH.FSDP = 1
# Partition-rule floor: param/optimizer leaves with fewer elements than this
# stay replicated (BN scales, biases — sharding them saves ~nothing and costs
# a collective). The census of what sharded is logged and journaled.
_C.MESH.FSDP_MIN_SIZE = 16384
# Sequence parallelism (parallel/seq.py, docs/PARALLELISM.md): >1 appends a
# trailing 'seq' axis to the training mesh and shards ACTIVATIONS along the
# token dimension — each seq-group device holds L/SEQ tokens (the journaled
# activation_bytes census is the measured 1/SEQ claim) and the attention
# contraction runs as MODEL.SEQ_ATTN (ring or Ulysses). The batch replicates
# along seq (a group cooperates on one shard), so global batch =
# BATCH_SIZE × DATA × FSDP, unchanged by SEQ. Must divide the model's token
# count (and the head count, for ulysses); requires a BatchNorm-free
# transformer arch (vit_*/mae_*). No -1 wildcard.
_C.MESH.SEQ = 1

# Dataplane (TPU addition; docs/DATA.md). `dtpu-dataplane --cfg ...` runs a
# disaggregated input service — a dispatcher owning the seed+epoch-keyed
# sample permutation plus N decode workers — and trainers opt in per run:
# the sample stream is bitwise-identical to local decode either way.
_C.DATA = CN()
# Where this run's loaders get batches: "" or "local" = decode on this host
# (the default per-host thread producer); "host:port" = stream from a
# running dtpu-dataplane dispatcher; "fleet" = the fleet controller
# co-schedules a service next to the gangs and injects its address via the
# DTPU_DATA_SERVICE env var (which always overrides this key).
_C.DATA.SERVICE = ""
# Dispatcher bind. PORT 0 derives a stable port from OUT_DIR
# (runtime/dist.derive_dataplane_port) so trainer hosts and the service
# agree on the address without parsing each other's output.
_C.DATA.HOST = "127.0.0.1"
_C.DATA.PORT = 0
# The address CLIENTS are told to connect to ("" = DATA.HOST). Separate
# because bind and connect addresses diverge the moment the fleet spans
# machines: a dispatcher bound to 0.0.0.0 must advertise its routable IP,
# never the bind wildcard (and never loopback, which every remote host
# resolves to itself).
_C.DATA.ADVERTISE_HOST = ""
# Decode worker pool: processes x threads (THREADS 0: cpu_count/WORKERS).
_C.DATA.WORKERS = 2
_C.DATA.WORKER_THREADS = 0
# Decoded-batch LRU cache, keyed by (shards, index range, transform
# fingerprint, epoch seed): multiple jobs / eval re-reads / epoch replays
# share one decode. Size it to a few epochs of the hot streams.
_C.DATA.CACHE_MB = 256
# A lease not completed within this window re-issues to another worker
# (a worker whose CONNECTION drops re-issues immediately; this clock only
# covers silently-wedged workers).
_C.DATA.LEASE_TIMEOUT_S = 30.0
# How many batches ahead of the slowest consumer the dispatcher keeps
# leased per stream (the decode-ahead depth, and the ready-buffer bound).
_C.DATA.WINDOW = 8
# Client behavior when the dispatcher dies mid-epoch: fall back to local
# decode at the exact next undelivered batch (bitwise-identical stream,
# typed dataplane_fallback journal record). Off = fail the run loudly.
_C.DATA.FALLBACK = True

# Fault tolerance (TPU addition; docs/FAULT_TOLERANCE.md). The reference has
# no mid-epoch failure story; these knobs govern the resilience layer.
_C.FAULT = CN()
# Jitted all-finite check on loss/grads: a non-finite step leaves params,
# optimizer state and BN stats untouched (bit-exact no-op for finite steps).
_C.FAULT.NONFINITE_GUARD = True
# Abort the run after this many consecutive skipped steps (divergence, not a
# one-off blip). Counted at PRINT_FREQ window granularity on the host.
_C.FAULT.MAX_CONSECUTIVE_SKIPS = 10
# Exponential-backoff-with-full-jitter retry knobs for flaky I/O (shard
# reads/decodes, dataset provisioning, checkpoint save/restore).
_C.FAULT.RETRY_ATTEMPTS = 3
_C.FAULT.RETRY_BASE_DELAY = 0.1
_C.FAULT.RETRY_MAX_DELAY = 2.0
# Graceful degradation: a sample that fails all retries is logged and
# substituted (zero image, weight 0) instead of killing the run.
_C.FAULT.DEGRADE = True
# Install the SIGTERM/SIGINT → graceful-preemption handler in train_model.
_C.FAULT.HANDLE_SIGNALS = True
# Distributed watchdog (docs/FAULT_TOLERANCE.md): seconds without step-loop
# progress before a rank dumps all-thread stacks, journals a ``hang`` event
# and exits nonzero (resilience.HANG_EXIT_CODE) — turning a dead peer in a
# collective into a bounded-time, diagnosed failure instead of a silent
# stall. 0 disables. Must comfortably exceed the first-step compile time.
_C.FAULT.HANG_TIMEOUT_S = 0.0
# Deterministic fault injection (test-only; DTPU_FAULT_* env vars override —
# see resilience.FaultInjector). All inert at these defaults.
_C.FAULT.INJECT_IO_INDICES = []
_C.FAULT.INJECT_IO_FAILURES = 1
_C.FAULT.INJECT_NAN_STEPS = []
_C.FAULT.INJECT_PREEMPT_STEP = -1
# Chaos modes: simulate a stalled step (sleep forever — the watchdog's prey)
# or a hard rank death (SIGKILL, no cleanup) exactly before this global step.
_C.FAULT.INJECT_HANG_STEP = -1
_C.FAULT.INJECT_KILL_STEP = -1

# Observability (TPU addition; docs/OBSERVABILITY.md). The structured
# telemetry subsystem: rank-0 JSONL metrics journal, MFU/goodput accounting,
# jax.monitoring counters, programmatic profiler windows, memory snapshots.
_C.OBS = CN()
# Master switch. When off, every telemetry call site degrades to a no-op.
_C.OBS.ENABLED = True
# Journal filename under OUT_DIR (JSONL, one typed record per line).
_C.OBS.JOURNAL = "telemetry.jsonl"
# os.fsync the journal after every record (power-loss-grade durability; the
# default already flushes per record, losing at most one torn line).
_C.OBS.FSYNC = False
# Price the jitted step with the XLA cost model (by LOWERING it — tracing
# only, no extra compile) and report MFU per window. Peak hardware FLOPs come
# from the built-in per-device_kind table; PEAK_TFLOPS_PER_DEVICE overrides
# (in TFLOP/s per JAX device; 0 = auto). Unknown hardware omits MFU.
_C.OBS.MFU = True
_C.OBS.PEAK_TFLOPS_PER_DEVICE = 0.0
# Programmatic profiler windows: capture PROFILE_STEPS steps with
# jax.profiler starting at each listed *global* step (epoch*steps_per_epoch
# + it), traces under OUT_DIR/profile/gstep_*. SIGUSR1 asks a live run for
# one window at the next step boundary (PROFILE_SIGUSR1 gates the handler).
_C.OBS.PROFILE_AT_STEPS = []
_C.OBS.PROFILE_STEPS = 5
_C.OBS.PROFILE_SIGUSR1 = True
_C.OBS.PROFILE_TOP_OPS = 20
# Live-array/HBM snapshot journaled at each epoch boundary.
_C.OBS.MEMORY_SNAPSHOTS = True
# Train-side tracing (obs/trace.py): journal typed `span` records per
# PRINT_FREQ window (data_wait / throttle / dispatch / fetch_wait / host
# phases, from counters the loop already feeds — zero added syncs) and per
# checkpoint dispatch.
_C.OBS.TRAIN_SPANS = True
# Declarative alarm rules (obs/alarms.py) evaluated by the live aggregator
# (the export sidecar, the serve frontend, the fleet controller — never the
# training process itself). Syntax: "name=metric<threshold" or
# "name=metric>threshold", with an optional ":for=N" hysteresis suffix
# (fire after N consecutive breaching evaluations; clear after N consecutive
# healthy ones). Per-model serve metrics (serve_p99_ms, serve_qps,
# serve_shed, serve_queue_depth) evaluate per hosted model. Fires/clears are
# journaled as typed alarm/alarm_clear records and invoke registered hooks
# (the fleet controller's hook journals fleet_alarm — the trigger the
# FLEET.AUTOSCALE policy acts on, docs/OBSERVABILITY.md "Alarms" and
# docs/FAULT_TOLERANCE.md "Autoscaled fleets").
_C.OBS.ALARMS = [
    "goodput_floor=goodput<0.1:for=3",
    "data_wait_ceiling=data_wait_frac>0.5:for=3",
    "heartbeat_stale=heartbeat_age_s>300",
    "skip_streak=consecutive_skips>3",
]
# Standalone Prometheus /metrics exporter port for supervisory processes
# (dtpu-agent, dtpu-fleet) and the default for the export sidecar
# (`python -m distribuuuu_tpu.obs export`). 0 disables the embedded
# exporter in agent/fleet; the serve frontend's /metrics rides its existing
# HTTP port and needs no extra port. HOST defaults to loopback — set
# "0.0.0.0" for a central Prometheus server to scrape across hosts.
_C.OBS.METRICS_PORT = 0
_C.OBS.METRICS_HOST = "127.0.0.1"
# Journal tail cadence for the live aggregators (sidecar / fleet / agent).
_C.OBS.TAIL_INTERVAL_S = 2.0

# In-job supervision (TPU addition; docs/FAULT_TOLERANCE.md "Supervised
# runs"). `python -m distribuuuu_tpu.agent --cfg ...` launches the training
# worker(s) as child processes and applies the exit-code recovery policy:
# hang (124) -> immediate restart into elastic resume; preemption/transient
# crash -> restart with exponential backoff + jitter under the restart
# budget; poison (117, persistent non-finite divergence) -> rollback
# escalation through progressively older known-good checkpoints.
_C.AGENT = CN()
# Worker processes (ranks) this agent launches on this host. >1 builds an
# agent-owned localhost rendezvous (RANK/WORLD_SIZE/MASTER_ADDR/MASTER_PORT).
_C.AGENT.NPROCS = 1
# Restart budget: give up once this many restarts happened inside the
# sliding RESTART_WINDOW_S window (failures older than the window age out,
# so a long-lived run is not killed by crashes it survived hours ago).
_C.AGENT.MAX_RESTARTS = 5
_C.AGENT.RESTART_WINDOW_S = 3600.0
# Exponential backoff (full jitter) between crash restarts; hang and
# preemption exits relaunch immediately (the run resumes where it stopped).
_C.AGENT.BACKOFF_BASE_S = 1.0
_C.AGENT.BACKOFF_MAX_S = 60.0
# Poison escalation: how many progressively-older known-good checkpoints to
# roll back through before giving up with a supervisor_verdict record.
_C.AGENT.MAX_ROLLBACKS = 2
# Supervisor-side hang detection: kill + restart the fleet when the obs
# journal stops growing for this long (0 disables). Complements the
# in-process watchdog (FAULT.HANG_TIMEOUT_S), which cannot fire when the
# whole process — watchdog thread included — is wedged or swapped out.
# The stall clock arms only after the journal's FIRST growth; until then
# (and for the first armed interval, which spans the cold compile) the
# separate HEARTBEAT_STARTUP_GRACE_S budget applies — a long first compile
# must never be killed as a hang. Grace 0 disables the pre-beat kill.
_C.AGENT.HEARTBEAT_TIMEOUT_S = 0.0
_C.AGENT.HEARTBEAT_STARTUP_GRACE_S = 900.0
# Preflight gate thresholds (every failed preflight is journaled and counts
# against the restart budget). MIN_FREE_DISK_GB 0 disables the disk check.
_C.AGENT.MIN_FREE_DISK_GB = 1.0
_C.AGENT.PREFLIGHT_DEVICE_PROBE = True
_C.AGENT.DEVICE_PROBE_TIMEOUT_S = 120.0
# After the first worker of a fleet exits, how long the others get to follow
# before the agent kills the stragglers (a dead peer leaves them wedged in a
# collective; the in-process watchdog usually beats this timer).
_C.AGENT.EXIT_BARRIER_S = 120.0
# After the agent itself is signaled (SIGTERM forwarded to the workers), how
# long a still-running fleet gets before being killed. Separate from (and
# effectively floored by) EXIT_BARRIER_S because a COOPERATING fleet needs
# this window for the agreed stop + the synchronous emergency checkpoint —
# a multi-GB save must never be SIGKILLed on the drain constant; the barrier
# here is only the backstop for a worker wedged in a dead collective.
_C.AGENT.STOP_BARRIER_S = 600.0
# Disarm the *chaos* fault injections (INJECT_KILL_STEP / INJECT_HANG_STEP /
# INJECT_PREEMPT_STEP) in relaunched workers: they model transient machine
# faults, and a gstep-keyed injection would otherwise re-fire on every
# replay, turning one injected fault into a crash loop. Data-poison
# injection (INJECT_NAN_STEPS) stays armed — persistent by design, it is
# what exercises the rollback escalation.
_C.AGENT.DISARM_CHAOS_ON_RESTART = True
# Custom worker command (whitespace-split; empty = the built-in worker,
# which runs trainer.train_model with this same --cfg/overrides argv).
# The agent appends nothing: rendezvous + recovery state ride env vars.
_C.AGENT.CMD = ""
# CPU fleets only: set --xla_force_host_platform_device_count=<N> in each
# worker's XLA_FLAGS (0 = leave the environment alone). How the CPU chaos
# tier gives every rank its own single-device "host".
_C.AGENT.CPU_DEVICES_PER_WORKER = 0
# Serving mode (docs/SERVING.md): supervise NPROCS independent dtpu-serve
# replicas instead of one collective training fleet. Replicas get per-rank
# frontend ports (SERVE.PORT + rank via DTPU_SERVE_PORT, preflight-checked
# with port_is_free) and are restarted INDIVIDUALLY on death — a replica
# kill is invisible to clients retrying across the replica set. Poison
# exits never attempt checkpoint rollback here (a serving replica has no
# checkpoints): they take the backoff/budget path with a typed reason.
_C.AGENT.SERVE = False
# Rolling replica restarts (serve mode): relaunch dead replicas ONE AT A
# TIME, gating the next relaunch on the previous one reporting ready via
# GET /healthz (version loaded, ladder compiled, no swap in flight) — so a
# multi-replica fleet never has more than one replica out of service at
# once. This is how long the agent waits for that readiness before rolling
# on anyway (a replica wedged at startup must not freeze the whole roll).
# 0 disables the gate (every dead replica relaunches immediately).
_C.AGENT.ROLLING_READY_S = 120.0
# Dataplane mode (docs/DATA.md): supervise one dtpu-dataplane service
# instead of a training fleet. Rides the exact restart budget / backoff /
# preflight machinery; the service has no checkpoints, so a poison exit
# takes the backoff path (the same resume-incapable-worker rule as serve).
_C.AGENT.DATAPLANE = False

# Serving (TPU addition; docs/SERVING.md). `dtpu-serve --cfg ...` hosts the
# model zoo behind a batched inference engine: AOT-compiled forward passes at
# the BATCH_SIZES ladder, Clipper-style dynamic micro-batching (coalesce
# pending requests, pad to the next compiled size, dispatch when full or when
# the queueing-delay bound expires), typed serve_* SLO records through the
# obs journal.
_C.SERVE = CN()
# The compiled batch ladder, ascending. Every request batch is padded up to
# the smallest listed size ≥ its example count; each size is AOT-compiled
# (jit().lower().compile()) per hosted model at startup, so steady-state
# serving never traces or compiles (CompileGuard-pinned in tests).
_C.SERVE.BATCH_SIZES = [1, 8, 32]
# Dynamic micro-batching: a dispatch happens when pending examples fill the
# largest compiled size OR the oldest queued request has waited this long —
# the knob trading p99 latency (low values) against batch fill (high values).
_C.SERVE.MAX_QUEUE_DELAY_MS = 5.0
# Backpressure: max pending examples per hosted model. A request that would
# exceed it is shed with HTTP 503 + a typed `serve_shed` journal record
# (never silently); the client-side retry (serve/client.py) absorbs sheds.
_C.SERVE.MAX_QUEUE_DEPTH = 256
# Hosted models: "name=arch@weights_path" entries, where weights_path is a
# converted-torch Orbax dir (scripts/convert_torch.py) or a trained
# checkpoint dir (OUT_DIR/checkpoints/ckpt_ep_NNN). Requests route by name.
# Empty: host one model from MODEL.ARCH + MODEL.WEIGHTS.
_C.SERVE.MODELS = []
# Frontend bind address. PORT 0 picks a free ephemeral port (printed and
# journaled); the DTPU_SERVE_PORT env var overrides (how the dtpu-agent
# serve mode gives each replica its own port without editing YAMLs).
_C.SERVE.HOST = "127.0.0.1"
_C.SERVE.PORT = 0
# "http" (ThreadingHTTPServer, POST /v1/predict + GET /healthz) or "stdin"
# (JSONL request per line on stdin, JSONL response per line on stdout).
_C.SERVE.MODE = "http"
# Input image side the ladder is compiled for (0 → TEST.CROP_SIZE) and the
# wire dtype ("uint8" raw pixels normalized on device — 4x smaller payloads —
# or "float32" pre-normalized).
_C.SERVE.IM_SIZE = 0
_C.SERVE.INPUT_DTYPE = "uint8"
# Served classes / compute dtype (0/"" → MODEL.NUM_CLASSES / MODEL.DTYPE).
_C.SERVE.NUM_CLASSES = 0
_C.SERVE.DTYPE = ""
# Execute each compiled ladder entry once at startup (loads executables,
# flushes lazy backend init) so the first real request doesn't pay it.
_C.SERVE.WARMUP = True
# Verify checkpoint integrity manifests before loading weights (corrupt
# weights fail the load loudly; unverified = no manifest is allowed).
_C.SERVE.VERIFY_INTEGRITY = True
# SLO accounting: a `serve_slo` record (p50/p99 latency, QPS, shed count,
# batch-fill histogram) per model every WINDOW_S seconds (and at shutdown).
# JOURNAL_REQUESTS additionally journals every request (serve_request) —
# exact but heavy; turn off for high-QPS deployments and keep the slo rollup.
_C.SERVE.SLO_WINDOW_S = 10.0
_C.SERVE.JOURNAL_REQUESTS = True
# Request tracing (obs/trace.py): journal typed `span` records per request
# (queue-wait / pad / execute / total) under the client-minted
# x-dtpu-trace-id. Same volume class as JOURNAL_REQUESTS — turn off for
# high-QPS deployments and keep the slo rollup.
_C.SERVE.TRACE_SPANS = True

# Continuous train->serve deployment (dtpu-deploy, serve/deploy.py;
# docs/SERVING.md "Continuous deployment"). WATCH_DIR non-empty arms a
# per-replica checkpoint watcher: new integrity-verified checkpoints in the
# watched directory (a training run's OUT_DIR or its checkpoints/ dir; via
# pathio, so gs:// works) are AOT-compiled ALONGSIDE the serving model (the
# incumbent keeps serving throughout — zero downtime by construction), given
# a canary fraction of live traffic, and promoted only when the canary's SLO
# and a quality delta on golden-fixture inputs both pass. A failing canary
# rolls back automatically (typed deploy_rollback record, per-checkpoint
# strike count persisted under OUT_DIR/deploy/).
_C.SERVE.DEPLOY = CN()
# Directory to poll for new checkpoints ("" disables deployment entirely).
_C.SERVE.DEPLOY.WATCH_DIR = ""
# Which hosted model the watcher deploys into ("" = the sole hosted model;
# required once SERVE.MODELS hosts more than one).
_C.SERVE.DEPLOY.MODEL = ""
# Watch poll cadence (seconds). Remote watch dirs pay one LIST per poll.
_C.SERVE.DEPLOY.POLL_S = 5.0
# Fraction of live traffic routed to the staged version during the canary
# window. Routing is by request hash (the client's trace id when present),
# so a retried request sticks to the version that first served it.
_C.SERVE.DEPLOY.CANARY_FRACTION = 0.1
# Canary window: promotion is decided after this many seconds of canary
# traffic, or as soon as MIN_CANARY_REQUESTS canary requests landed.
_C.SERVE.DEPLOY.CANARY_S = 30.0
_C.SERVE.DEPLOY.MIN_CANARY_REQUESTS = 20
# SLO gate: the canary's p99 must stay within this factor of the
# incumbent's live p99 (from the in-process aggregator's serve_slo state).
# No incumbent p99 yet (idle replica) passes vacuously.
_C.SERVE.DEPLOY.SLO_P99_FACTOR = 2.0
# Quality gate on GATE_N deterministic golden-fixture inputs (the same
# input family the quant gate uses): candidate logits must be finite, agree
# with the incumbent's top-1 on at least MIN_TOP1_AGREE of them, and (when
# MAX_LOGIT_RMSE > 0) stay within the RMSE bound. Looser than the quant
# gate by design — a newer training checkpoint legitimately moves logits;
# the gate exists to catch poisoned/garbage weights, not training progress.
_C.SERVE.DEPLOY.GATE_N = 16
_C.SERVE.DEPLOY.GATE_SEED = 0
_C.SERVE.DEPLOY.MIN_TOP1_AGREE = 0.5
_C.SERVE.DEPLOY.MAX_LOGIT_RMSE = 0.0
# Rollback escalation (PR 5's poison-rollback, serving-side): each rollback
# bumps the checkpoint's persisted strike count; a checkpoint at
# MAX_STRIKES is never tried again (a poison checkpoint cannot flap the
# fleet forever). Strikes live in OUT_DIR/deploy/strikes.json and survive
# replica restarts.
_C.SERVE.DEPLOY.MAX_STRIKES = 2
# Rolling-update lease: replicas serialize their rollouts through a lease
# file under OUT_DIR/deploy/, so one replica stages/canaries at a time and
# fleet capacity never drops. A holder silent for this long is presumed
# dead and its lease taken over.
_C.SERVE.DEPLOY.LOCK_LEASE_S = 600.0

# Global serving front door (dtpu-ingress, serve/ingress.py; docs/SERVING.md
# "Global ingress"). A router process in front of N replica pools:
# discovery by /healthz + /metrics polling, least-loaded routing with
# trace-id stickiness inside the home pool, spillover to secondary pools
# before shedding, per-tenant token-bucket admission, and an active/standby
# router pair over a stale-takeover lease file.
_C.SERVE.INGRESS = CN()
# Replica pools behind the router: "pool=host:port,host:port,..." entries
# (a bare port means 127.0.0.1). The FIRST entry is the home pool; a
# saturated or dark home pool spills to the remaining pools in listed
# order. Empty disables the router entirely.
_C.SERVE.INGRESS.POOLS = []
# Router bind address. PORT 0 picks a free ephemeral port; the
# DTPU_INGRESS_PORT env var overrides (how the fleet sidecar hands each
# router of an active/standby pair its own port).
_C.SERVE.INGRESS.HOST = "127.0.0.1"
_C.SERVE.INGRESS.PORT = 0
# Discovery cadence: every PROBE_S each configured replica is polled
# (/healthz for liveness+readiness+models, /metrics for the queue-depth /
# p99 / fill gauges its routing weight derives from). A replica that fails
# a probe is quarantined for QUARANTINE_S, then re-probed — late-appearing
# replicas join the pool live through the same loop.
_C.SERVE.INGRESS.PROBE_S = 1.0
_C.SERVE.INGRESS.PROBE_TIMEOUT_S = 2.0
_C.SERVE.INGRESS.QUARANTINE_S = 5.0
# Routing: requests go least-loaded within the home pool, but a request
# carrying a trace id prefers its rendezvous-hashed replica (retries land
# on the same machine — warm caches, coherent spans) until that replica's
# load exceeds the pool minimum by STICKY_SLACK examples.
_C.SERVE.INGRESS.STICKY_SLACK = 8.0
# Per-request candidates tried per pool before moving to the next pool.
_C.SERVE.INGRESS.ATTEMPTS_PER_POOL = 2
# Upstream predict timeout per attempt (seconds).
_C.SERVE.INGRESS.TIMEOUT_S = 30.0
# Tenancy: "name=key:rps[:burst[:weight]]" entries. A non-empty list makes
# the x-dtpu-api-key header mandatory on /v1/predict (unknown key -> 401).
# Each tenant's token bucket refills at `rps` examples/second with `burst`
# capacity (default 2x rps); quota exhaustion sheds 429 + Retry-After
# sized to the bucket's refill, never a silent drop. `weight` (default 1)
# sets the tenant's share of router capacity under saturation.
_C.SERVE.INGRESS.TENANTS = []
# Weighted-fair admission: once the router's total in-flight examples
# reach MAX_INFLIGHT, a tenant holding more than
# weight/sum(weights) * MAX_INFLIGHT of them is shed (429) until it
# drains — one tenant's burst degrades that tenant, never a sibling's SLO.
_C.SERVE.INGRESS.MAX_INFLIGHT = 64
# Active/standby failover: both routers of a pair run the same config with
# DTPU_INGRESS_INSTANCE 0/1; whoever holds the lease file
# (OUT_DIR/ingress/router.lock, the deploy rollout-lease protocol) serves,
# the other answers 503 "standby" (retryable) and probes for takeover. A
# holder silent for LEASE_S is presumed dead; the standby promotes within
# about one lease interval.
_C.SERVE.INGRESS.LEASE_S = 2.0
# Per-tenant rollup cadence (ingress_tenant records) and per-request
# journaling (ingress_route; heavy — same class as SERVE.JOURNAL_REQUESTS).
_C.SERVE.INGRESS.ROLLUP_S = 10.0
_C.SERVE.INGRESS.JOURNAL_REQUESTS = True
# Fleet co-scheduling: FLEET True makes the dtpu-fleet controller spawn
# REPLICAS router process(es) beside its gangs (the DataplaneSidecar
# pattern — restart-on-death under the fleet restart budget; 2 = an
# active/standby pair on PORT, PORT+1).
_C.SERVE.INGRESS.FLEET = False
_C.SERVE.INGRESS.REPLICAS = 1

# Post-training int8 quantization (dtpu-quant; docs/PERFORMANCE.md,
# docs/SERVING.md "Serving int8"). A hosted model opts in per entry:
# SERVE.MODELS "name=arch@weights:int8" quantizes that model's conv/dense
# weights per-channel symmetric int8 (BatchNorm folded where possible),
# calibrates per-tensor activation scales over CALIB_BATCHES synthetic
# batches, and AOT-compiles the int8×int8→int32 forward at the same
# SERVE.BATCH_SIZES ladder — the MXU's int8 rate is 2x bf16.
_C.QUANT = CN()
# Calibration pass: batches run through the fp model to record activation
# amax per layer. Synthetic inputs in the serve wire dtype (seeded, so the
# quantized model is reproducible); point a real-traffic replay at the
# engine's calibrate hook for production-distribution scales.
_C.QUANT.CALIB_BATCHES = 4
_C.QUANT.CALIB_BATCH_SIZE = 8
_C.QUANT.CALIB_SEED = 1234
# Quality gate (quant/gate.py): compare the int8 path against the fp32
# engine on GATE_N deterministic fixture inputs (convert.golden_inputs —
# the same input family the checked-in tests/fixtures goldens pin). Either
# threshold failing REFUSES to serve the model and the measurement is
# journaled as a typed `quant_quality` record either way. GATE False skips
# the refusal (the record is still written) — escape hatch, not a default.
_C.QUANT.GATE = True
_C.QUANT.GATE_N = 16
_C.QUANT.GATE_SEED = 0
_C.QUANT.MIN_TOP1_AGREE = 0.99
_C.QUANT.MAX_LOGIT_RMSE = 0.25
# Quantization-aware fine-tuning (quant/qat.py; docs/PERFORMANCE.md
# "Quantized training"). QAT True routes every train/eval forward through
# the fake-quant straight-through-estimator interception: activations
# fake-quantized per-tensor on scales from the same calibration pass PTQ
# uses (CALIB_* knobs above), weights per-output-channel on their live
# amax. The rescue path for a model that fails the PTQ serve gate —
# fine-tune with QAT on, re-serve `:int8`, the gate/fixtures/refuse-to-
# serve plumbing transfer unchanged.
_C.QUANT.QAT = False
# Fake-quant grid: "int8" (the serving grid, ±127 symmetric) or "fp8"
# (float8_e4m3fn — the Micikevicius 2022 training format, ±448).
_C.QUANT.QAT_MODE = "int8"
# Self-distillation weight: adds QAT_DISTILL · mean((fp_logits −
# qat_logits)²) to the loss, regressing the fake-quant forward onto the
# model's own (stop-gradient) fp logits — the serve gate's logit-RMSE
# metric optimized directly. 0 = pure task-loss QAT; ~1.0 is the
# documented rescue recipe.
_C.QUANT.QAT_DISTILL = 0.0

# Fleet orchestration (TPU addition; docs/FAULT_TOLERANCE.md "Fleet runs").
# `dtpu-fleet --cfg ...` promotes supervision from host scope (dtpu-agent)
# to cluster scope: gang-scheduled multi-host launches through a lightweight
# rendezvous service (the controller assigns RANK/WORLD_SIZE/MASTER_ADDR/
# MASTER_PORT and a fleet epoch), whole-host failure recovery (gang restart
# at reduced size into elastic resume), scale-up rejoin of healed hosts at
# the next checkpoint boundary (cooperative FLEET resize stop), and a
# priority multi-job queue with bounded-drain preemption over one pool.
_C.FLEET = CN()
# Host slots in the pool (each runs one fleet-managed dtpu-agent with
# NPROCS_PER_HOST worker ranks). The controller launches them as local
# child processes — on one machine this simulates an N-host gang (the CPU
# chaos tier); the rendezvous protocol itself is multi-host shaped.
_C.FLEET.HOSTS = 2
_C.FLEET.NPROCS_PER_HOST = 1
# Rendezvous service bind (PORT 0 picks a free ephemeral port) and the
# address workers use for MASTER_ADDR (the host carrying global rank 0).
_C.FLEET.HOST = "127.0.0.1"
_C.FLEET.PORT = 0
_C.FLEET.MASTER_ADDR = "127.0.0.1"
# Stable job id; the gang's rendezvous MASTER_PORT is derived
# deterministically from "<job_id>:epoch<E>" (runtime/dist.py
# derive_rendezvous_port) so re-formed gangs never race independent port
# picks across hosts. "" derives the id from OUT_DIR.
_C.FLEET.JOB_ID = ""
# Gang restart budget + backoff — same sliding-window semantics as AGENT.*,
# one scope up: a gang restart is one spend, however many hosts relaunch.
_C.FLEET.MAX_GANG_RESTARTS = 5
_C.FLEET.RESTART_WINDOW_S = 3600.0
_C.FLEET.BACKOFF_BASE_S = 1.0
_C.FLEET.BACKOFF_MAX_S = 60.0
# Fleet-scope poison escalation (mirrors AGENT.MAX_ROLLBACKS: each gang-wide
# poison exit rolls auto-resume one known-good checkpoint further back).
_C.FLEET.MAX_ROLLBACKS = 2
# Controller-side journal heartbeat over the WHOLE journal (main + parts):
# a gang whose journal stops growing is killed and gang-restarted. Same
# armed-after-first-beat + startup-grace semantics as the agent's.
_C.FLEET.HEARTBEAT_TIMEOUT_S = 0.0
_C.FLEET.HEARTBEAT_STARTUP_GRACE_S = 900.0
# Never re-form a gang below this many hosts; with fewer healthy slots the
# controller waits (under the restart budget) for hosts to heal.
_C.FLEET.MIN_HOSTS = 1
# A slot whose host died is quarantined this long before it may rejoin
# (a real deployment replaces this clock with a health probe; the clock is
# the simulation-grade stand-in and the floor under probe flapping).
_C.FLEET.HOST_COOLDOWN_S = 30.0
# Elastic scale-up: let healed hosts rejoin a RUNNING reduced gang. The
# rejoin is cooperative — the controller bumps the fleet epoch, survivors
# checkpoint-and-exit at an agreed step (resilience.FleetSignalPoller), and
# the gang relaunches at N+1 hosts into elastic resume.
_C.FLEET.REJOIN = True
# Only trigger the rejoin resize after the reduced gang has committed a NEW
# checkpoint since its launch — proof of forward progress, so resize churn
# can never starve a struggling gang ("rejoin at the next checkpoint
# boundary" is literal).
_C.FLEET.REJOIN_AFTER_CHECKPOINT = True
# Bounded drain for cooperative stops (resize / job preemption / shutdown):
# after announcing the stop, hosts get DRAIN_S to checkpoint and exit; then
# SIGTERM; after another DRAIN_S, SIGKILL. Covers the emergency-checkpoint
# write at the agreed stop step.
_C.FLEET.DRAIN_S = 120.0
# Multi-job queue over the pool: "name=priority@command" entries (higher
# priority wins; equal priority is FIFO). A job submitted while a lower-
# priority job runs preempts it via the bounded drain above (SIGTERM ->
# emergency checkpoint), runs, and the preempted job relaunches into
# elastic resume. Jobs can also be submitted to a RUNNING controller by
# dropping {"name","priority","hosts","cmd"} JSON files into
# OUT_DIR/fleet/queue/. Empty: one built-in training job (the same worker
# the dtpu-agent launches) using this config's argv.
_C.FLEET.QUEUE = []

# SLO-driven autoscaling (fleet_autoscale.py; docs/FAULT_TOLERANCE.md
# "Autoscaled fleets"). The closed control loop over the OBS.ALARMS rules:
# the controller's fleet_alarm hook and the live aggregator's gauges drive
# an AutoscalePolicy that scales serving replicas, preempts/resumes
# training for traffic spikes, and co-scales dataplane decode workers.
# Every decision is a typed fleet_scale journal record; per-resource
# hysteresis (cooldown + sustained-health window + min/max bounds) keeps
# capacity from oscillating under an alarm storm.
_C.FLEET.AUTOSCALE = CN()
_C.FLEET.AUTOSCALE.ENABLE = False
# Serving-replica bounds and step. MIN is the capacity floor a scale-down
# can never cross; MAX both caps scale-up and sizes the agent's slot table
# (the dtpu-agent serving mode allocates ports for max(AGENT.NPROCS, MAX)
# slots up front, so a scale-up never races an ephemeral port pick).
_C.FLEET.AUTOSCALE.SERVE_MIN = 1
_C.FLEET.AUTOSCALE.SERVE_MAX = 4
_C.FLEET.AUTOSCALE.SERVE_STEP = 1
# Which alarm METRICS mean "the serving tier is hurting" — an active
# fleet_alarm on any of these is the scale-up (and training-preemption)
# trigger. Names match the per-model serve gauges the aggregator tracks.
_C.FLEET.AUTOSCALE.SERVE_UP_METRICS = [
    "serve_p99_ms", "serve_shed", "serve_queue_depth",
]
# Per-resource hysteresis. COOLDOWN_S: minimum wall time between two
# capacity changes of the SAME resource (the flap clamp — an alarm storm
# firing/clearing every evaluation produces exactly one change per
# cooldown, pinned by tests/test_autoscale.py). DOWN_STABLE_S: how long
# the resource must be continuously healthy (no up-alarm active, fill
# below the floor) before any scale-down / training resume — every
# re-fire resets the clock, so oscillating alarms can never shrink
# capacity they just asked for.
_C.FLEET.AUTOSCALE.COOLDOWN_S = 60.0
_C.FLEET.AUTOSCALE.DOWN_STABLE_S = 120.0
# Fill collapse: scale serving down only when every hosted model's
# serve_mean_fill gauge sits at or below this AND no queue is backed up —
# "the fleet is padding batches for nobody", the inverse of the p99 spike.
_C.FLEET.AUTOSCALE.FILL_FLOOR = 0.25
# Traffic spikes may preempt training via the existing priority-queue
# cooperative-stop protocol (emergency checkpoint, elastic resume when
# the spike clears) — training capacity is the scale-up reservoir.
_C.FLEET.AUTOSCALE.PREEMPT_TRAINING = True
# Dataplane co-scaling on data_wait_frac alarms: the fleet-owned input
# service respawns with more decode workers (trainers ride the
# DATA.FALLBACK local-decode gap), stepping DATA_STEP at a time up to
# DATA_MAX; sustained health steps back down toward DATA.WORKERS.
_C.FLEET.AUTOSCALE.DATA_MAX = 8
_C.FLEET.AUTOSCALE.DATA_STEP = 2

# Resume policy (TPU addition). Epoch checkpoints stay the primary contract;
# these govern the extra step-granular/robustness behavior on top.
_C.RESUME = CN()
# Consider mid-epoch emergency checkpoints (preemption saves) when resuming.
_C.RESUME.STEP_GRANULAR = True
# A corrupt/partial highest checkpoint is skipped with a warning (fall back
# to the next-highest) instead of crashing the restart loop.
_C.RESUME.SKIP_CORRUPT = True
# Verify the per-file checksum manifest before restoring a checkpoint; a
# failed verify QUARANTINES the directory (rename to ``corrupt_*``, typed
# journal event) and restore_latest falls back to the next-oldest.
_C.RESUME.VERIFY_INTEGRITY = True
# Rollback depth: auto-resume skips this many of the most-advanced
# *known-good* (integrity-verified) checkpoints and restores an older one.
# The dtpu-agent's poison escalation drives this via the
# DTPU_RESUME_ROLLBACK env var (env wins, so the agent never edits YAMLs);
# operators can set it by hand to back a diverged run out of a bad basin.
_C.RESUME.ROLLBACK = 0

# Output directory
_C.OUT_DIR = "./exp"
_C.CFG_DEST = "config.yaml"

_C.RNG_SEED = None

_CFG_DEFAULT = _C.clone()
_CFG_DEFAULT.freeze()


def get_default(key_path: str):
    """Default value for a dotted config key (e.g. ``"TEST.DATASET"``)."""
    node = _CFG_DEFAULT
    for part in key_path.split("."):
        node = node[part]
    return node


def merge_from_file(cfg_file: str) -> None:
    _C.merge_from_file(cfg_file)


def dump_cfg() -> None:
    """Dump the config to OUT_DIR/CFG_DEST (provenance, `config.py:75-79`).

    Through pathio so OUT_DIR may be an object store — the reference routes
    this through g_pathmgr (`config.py:70-78`) for the same reason."""
    from distribuuuu_tpu.runtime import pathio

    pathio.makedirs(_C.OUT_DIR)
    cfg_file = pathio.join(_C.OUT_DIR, _C.CFG_DEST)
    with pathio.open_write(cfg_file) as f:
        _C.dump(stream=f)


def reset_cfg() -> None:
    """Reset config to initial state (leaves the singleton mutable)."""
    _C.defrost()
    _C.clear()
    for k, v in _CFG_DEFAULT.clone().items():
        _C[k] = v


def load_cfg_fom_args(description: str = "Config file options.", argv=None) -> None:
    """Load config from command line arguments and set any specified options.

    CLI contract identical to the reference (`config.py:87-100`): ``--cfg`` for
    the YAML, a ``--local_rank`` flag accepted-and-ignored for launcher
    compatibility, and a trailing ``KEY VALUE ...`` remainder of overrides.
    (The name's typo is preserved deliberately — it is public API.)
    """
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument("--cfg", dest="cfg_file", help="Config file location", default=None, type=str)
    parser.add_argument(
        "--local_rank",
        help="accepted for launcher compatibility; JAX is one process per host",
        default=None,
    )
    parser.add_argument(
        "opts",
        help="See distribuuuu_tpu/config.py for all options",
        default=None,
        nargs=argparse.REMAINDER,
    )
    args = parser.parse_args(sys.argv[1:] if argv is None else argv)
    if args.cfg_file is not None:
        merge_from_file(args.cfg_file)
    if args.opts:
        _C.merge_from_list(args.opts)
