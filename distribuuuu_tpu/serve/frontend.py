"""dtpu-serve frontend: HTTP / stdin-JSONL request ingress + replica main.

Same config contract as train_net.py (``--cfg config/x.yaml KEY VALUE ...``;
``dtpu-serve`` console script / ``python -m distribuuuu_tpu.serve``). One
replica = one process = one engine + batcher + frontend; the dtpu-agent's
serving mode (``AGENT.SERVE True``) keeps N of them alive, handing each its
port via ``DTPU_SERVE_PORT`` (docs/SERVING.md).

HTTP surface (deliberately minimal — a mesh-routable JSON contract, not a
framework):

- ``POST /v1/predict`` — body ``{"model": name, "inputs": ...}`` where
  inputs is a nested list ``(n, H, W, 3)`` or ``{"b64": <base64 raw bytes>,
  "shape": [n, H, W, 3]}`` in ``SERVE.INPUT_DTYPE``. 200 → ``{"model":
  name, "logits": [[...]], "latency_ms": x}``; 503 → shed (retry);
  400/404 → client error.
- ``GET /healthz`` — ``{"status": "ok", "models": [...], "replica": i}``;
  the agent's preflight and the client's liveness probe both read it.
- ``GET /metrics`` — Prometheus text of the replica's live aggregate
  (p50/p99/QPS/queue-depth per model, shed and batch counters; dtpu-obs v2,
  docs/OBSERVABILITY.md "Live metrics").

Requests may carry an ``x-dtpu-trace-id`` header (the serve client mints
one); the queue-wait/pad/execute/total phases of the request are journaled
as typed ``span`` records under that id and the header is echoed back.

Stdin mode (``SERVE.MODE stdin``): one JSON request per line on stdin, one
JSON response per line on stdout — the zero-socket smoke path.
"""

from __future__ import annotations

import base64
import binascii
import json
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from distribuuuu_tpu.config import cfg, load_cfg_fom_args
from distribuuuu_tpu.logging import logger, setup_logger
from distribuuuu_tpu.obs.alarms import engine_from_cfg
from distribuuuu_tpu.obs.exporter import (
    PROM_CONTENT_TYPE,
    merged_snapshot,
    render_prometheus,
)
from distribuuuu_tpu.obs.journal import ValidatedJournal
from distribuuuu_tpu.obs.stream import LiveAggregator
from distribuuuu_tpu.obs.trace import TRACE_HEADER, ensure_trace_id, span_fields
from distribuuuu_tpu.serve.batcher import MicroBatcher, QueueFullError, SLOTracker
from distribuuuu_tpu.serve.engine import InferenceEngine, ModelSpec, parse_model_specs


# ---------------------------------------------------------------------------
# Journal glue (typed serve_* records into OUT_DIR's telemetry journal)
# ---------------------------------------------------------------------------

class ServeJournal(ValidatedJournal):
    """Validated ``serve_*`` appends, one single-writer file per process.

    A SUPERVISED replica (``DTPU_SERVE_REPLICA`` set by the agent) must not
    append to the journal the agent — and its sibling replicas — are
    writing: the `Journal` contract is one writer per file (its lock is
    per-process, its startup torn-tail healing assumes no live co-writer,
    and a SIGKILL mid-append would glue the next process's record onto the
    torn line mid-file, which `read_journal` rightly treats as corruption).
    Each supervised replica therefore owns ``telemetry.jsonl.part<1000+R>``
    — the part-continuation naming `read_journal`/`validate_journal`
    already reassemble, offset by 1000 to stay clear of remote commit
    parts — so ``obs summarize OUT_DIR/telemetry.jsonl`` still renders the
    whole supervised story from one path. Standalone replicas (no env) own
    the main file outright.
    """

    def __init__(self, out_dir: str):
        try:
            from distribuuuu_tpu.obs.telemetry import journal_path
            from distribuuuu_tpu.runtime import pathio

            path = journal_path(out_dir)
            replica_env = os.environ.get("DTPU_SERVE_REPLICA")
            if replica_env is not None and not pathio.is_remote(path):
                path = f"{path}.part{1000 + int(replica_env)}"
        except Exception as exc:  # pragma: no cover - defensive
            logger.warning(f"serve journal unavailable: {exc!r}")
            path = None
        super().__init__(path, label="serve journal")


# ---------------------------------------------------------------------------
# Request decoding
# ---------------------------------------------------------------------------

class BadRequest(ValueError):
    """Client-side error (HTTP 400): malformed body, wrong shape/dtype."""


def decode_inputs(payload, im_size: int, dtype: np.dtype) -> np.ndarray:
    """Decode a request's ``inputs`` field to ``(n, im_size, im_size, 3)``."""
    if isinstance(payload, dict):
        try:
            raw = base64.b64decode(payload["b64"], validate=True)
            shape = tuple(int(d) for d in payload["shape"])
        except (KeyError, TypeError, ValueError, binascii.Error) as exc:
            raise BadRequest(f"bad b64 inputs: {exc!r}") from exc
        try:
            arr = np.frombuffer(raw, dtype=dtype).reshape(shape)
        except ValueError as exc:
            raise BadRequest(f"b64 payload does not match shape {shape}: {exc}") from exc
    else:
        try:
            arr = np.asarray(payload)
            if arr.dtype != dtype:
                if dtype == np.uint8 and arr.dtype.kind not in "iu":
                    # float pixels into a uint8 server would TRUNCATE to
                    # garbage (0.5 -> 0) and return confident logits for a
                    # black image — refuse loudly instead
                    raise BadRequest(
                        f"inputs are {arr.dtype} but this server's wire "
                        f"dtype is uint8 raw pixels (SERVE.INPUT_DTYPE) — "
                        f"send integer 0..255 values, or a float32 server"
                    )
                if dtype == np.uint8 and arr.size and (
                    int(arr.min()) < 0 or int(arr.max()) > 255
                ):
                    raise BadRequest(
                        "uint8 pixel values must be in 0..255 "
                        f"(got {int(arr.min())}..{int(arr.max())})"
                    )
                arr = arr.astype(dtype)
        except (TypeError, ValueError) as exc:
            raise BadRequest(f"inputs not convertible to {dtype}: {exc!r}") from exc
    if arr.ndim == 3:  # single example: implicit batch of 1
        arr = arr[None]
    if arr.ndim != 4 or arr.shape[0] < 1 or arr.shape[1:] != (im_size, im_size, 3):
        raise BadRequest(
            f"inputs shape {arr.shape} != (n>=1, {im_size}, {im_size}, 3) "
            f"(SERVE.IM_SIZE={im_size})"
        )
    return np.ascontiguousarray(arr)


# ---------------------------------------------------------------------------
# The replica: engine + batcher + SLO + one ingress
# ---------------------------------------------------------------------------

class ServeReplica:
    """Everything one serving process owns, wired together."""

    def __init__(self, mesh, specs: list[ModelSpec], out_dir: str):
        s = cfg.SERVE
        self.im_size = int(s.IM_SIZE) or int(cfg.TEST.CROP_SIZE)
        self.input_dtype = np.dtype(str(s.INPUT_DTYPE))
        self.replica = int(os.environ.get("DTPU_SERVE_REPLICA", "0"))
        self.journal = ServeJournal(out_dir)
        self.journal_requests = bool(s.JOURNAL_REQUESTS)
        self.trace_spans = bool(s.TRACE_SPANS)
        # live telemetry plane (dtpu-obs v2): every journaled record also
        # folds into the in-process aggregator — a replica must not tail
        # its own open journal, and the fold is O(fields) host work — so
        # GET /metrics renders current state with zero extra I/O, and the
        # OBS.ALARMS rules evaluate on every SLO rollup
        self.aggregator = LiveAggregator()
        # heartbeat_age_s rules excluded: an idle replica journals nothing
        # but is not dead — /healthz owns serve liveness
        self.alarms = engine_from_cfg(
            self.journal_event, exclude_metrics=("heartbeat_age_s",)
        )
        self.slo = SLOTracker(
            self.journal_event,
            window_s=float(s.SLO_WINDOW_S),
            on_flush=self._evaluate_alarms,
        )
        self.slo.replica = self.replica
        self.engine = InferenceEngine(
            mesh,
            batch_sizes=list(s.BATCH_SIZES),
            im_size=self.im_size,
            num_classes=int(s.NUM_CLASSES) or int(cfg.MODEL.NUM_CLASSES),
            input_dtype=str(s.INPUT_DTYPE),
            compute_dtype=str(s.DTYPE) or str(cfg.MODEL.DTYPE),
            verify_integrity=bool(s.VERIFY_INTEGRITY),
            journal_event=self.journal_event,
            quant_cfg={
                "calib_batches": int(cfg.QUANT.CALIB_BATCHES),
                "calib_batch_size": int(cfg.QUANT.CALIB_BATCH_SIZE),
                "calib_seed": int(cfg.QUANT.CALIB_SEED),
                "gate": bool(cfg.QUANT.GATE),
                "gate_n": int(cfg.QUANT.GATE_N),
                "gate_seed": int(cfg.QUANT.GATE_SEED),
                "min_top1_agree": float(cfg.QUANT.MIN_TOP1_AGREE),
                "max_logit_rmse": float(cfg.QUANT.MAX_LOGIT_RMSE),
            },
        )
        self.engine.load_all(specs)
        warmup_s = self.engine.warmup() if s.WARMUP else 0.0
        self.batcher = MicroBatcher(
            self.engine.runner(),
            {name: self.engine.models[name].batch_sizes for name in self.engine.models},
            max_delay_ms=float(s.MAX_QUEUE_DELAY_MS),
            max_depth=int(s.MAX_QUEUE_DEPTH),
            journal_event=self.journal_event,
            slo=self.slo,
            timed_runner=self.engine.forward_timed,
            trace_spans=self.trace_spans,
        ).start()
        # continuous deployment (serve/deploy.py): a non-empty WATCH_DIR
        # arms the per-replica checkpoint watcher — hot reload, canary
        # gating, automatic rollback (docs/SERVING.md "Continuous
        # deployment"). The watcher owns readiness: /healthz reports
        # ready=False while a version swap is in flight.
        self.deploy = None
        if str(s.DEPLOY.WATCH_DIR):
            from distribuuuu_tpu.serve.deploy import DeployManager, DeploySettings

            self.deploy = DeployManager(
                DeploySettings.from_cfg(s.DEPLOY),
                engine=self.engine,
                batcher=self.batcher,
                aggregator=self.aggregator,
                journal_event=self.journal_event,
                out_dir=out_dir,
                replica=self.replica,
            ).start()
        self.port = 0  # bound ingress port (http mode fills it in)
        self._warmup_s = warmup_s

    def is_ready(self) -> bool:
        """False exactly while a deploy version swap is in flight — the
        rolling-restart gate (the replica still SERVES while not ready;
        readiness gates rollout/restart orchestration, not traffic)."""
        return self.deploy is None or self.deploy.ready

    def journal_event(self, kind: str, **fields) -> None:
        """Journal one typed record AND fold it into the live aggregator."""
        self.journal.event(kind, **fields)
        try:
            self.aggregator.ingest({"ts": time.time(), "kind": kind, **fields})
        except Exception:  # pragma: no cover - the fold is already defensive
            pass

    def _evaluate_alarms(self) -> None:
        if self.alarms is not None:
            self.alarms.evaluate(self.aggregator.snapshot())

    def metrics_text(self) -> str:
        """Prometheus exposition of the replica's live aggregate state
        (GET /metrics). Alarm rules are evaluated per scrape too, so a
        breach is detected even when traffic — and with it the SLO rollup
        cadence — has collapsed."""
        self._evaluate_alarms()
        return render_prometheus(merged_snapshot(self.aggregator, self.alarms))

    def announce(self, port: int) -> None:
        self.port = int(port)
        self.journal_event(
            "serve_start",
            models=sorted(self.engine.models),
            batch_sizes=self.engine.batch_sizes,
            port=self.port,
            replica=self.replica,
            host=str(cfg.SERVE.HOST),
            aot_compiles=int(self.engine.aot_compiles),
            warmup_s=round(self._warmup_s, 3),
            input_dtype=str(self.input_dtype),
        )

    def predict(
        self, model: str, inputs: np.ndarray, trace_id: str | None = None
    ) -> tuple[np.ndarray, float]:
        """Batched inference for one request; returns (logits, latency_ms).

        ``trace_id`` (the validated ``x-dtpu-trace-id``, minted here for
        header-less callers) rides the request through the batcher into the
        engine dispatch; the queue-wait/pad/execute spans land there and the
        ``total`` span — the latency the client saw — lands here.
        """
        trace_id = ensure_trace_id(trace_id) if self.trace_spans else trace_id
        tic = time.monotonic()
        try:
            logits = self.batcher.submit(model, inputs, trace_id=trace_id)
        except QueueFullError:
            raise
        except (KeyError, ValueError) as exc:
            # unknown model / oversize request: the CLIENT's fault — a 400,
            # never a retryable 500 (replaying a doomed request against every
            # replica until the deadline) and never a replica-killing crash
            # in stdin mode
            raise BadRequest(str(exc)) from exc
        latency_ms = 1000.0 * (time.monotonic() - tic)
        self.slo.request(model, latency_ms)
        n = int(inputs.shape[0])
        if self.trace_spans and trace_id:
            self.journal_event(
                "span",
                **span_fields(trace_id, "total", latency_ms, model=model, n=n, ok=True),
            )
        if self.journal_requests:
            extra = {"trace_id": trace_id} if trace_id else {}
            self.journal_event(
                "serve_request",
                model=model,
                n=n,
                latency_ms=round(latency_ms, 3),
                ok=True,
                **extra,
            )
        return logits, latency_ms

    def handle(self, body: dict, trace_id: str | None = None) -> dict:
        """One decoded request dict → response dict (shared by http/stdin)."""
        model = body.get("model", "")
        trace_id = ensure_trace_id(trace_id or body.get("trace_id"))
        inputs = decode_inputs(body.get("inputs"), self.im_size, self.input_dtype)
        logits, latency_ms = self.predict(model, inputs, trace_id=trace_id)
        return {
            "model": model,
            "logits": logits.tolist(),
            "latency_ms": round(latency_ms, 3),
            "trace_id": trace_id,
        }

    def shutdown(self) -> None:
        if self.deploy is not None:
            self.deploy.stop()
        self.batcher.stop()
        self.slo.flush()
        self.journal.close()


# ---------------------------------------------------------------------------
# HTTP ingress
# ---------------------------------------------------------------------------

def _make_handler(replica: ServeReplica):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _reply(
            self,
            code: int,
            payload: dict,
            trace_id: str | None = None,
            retry_after_s: float | None = None,
        ) -> None:
            data = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            if trace_id:  # echo the id so callers can correlate journal spans
                self.send_header(TRACE_HEADER, trace_id)
            if retry_after_s is not None:
                # queue-depth-derived shed hint: when THIS replica expects
                # to have drained its backlog. Decimal seconds — our client
                # parses floats; RFC-9110 integer readers round up.
                self.send_header("Retry-After", f"{retry_after_s:.3f}")
            self.end_headers()
            self.wfile.write(data)

        def _reply_text(self, code: int, text: str, ctype: str) -> None:
            data = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):  # noqa: N802 (stdlib naming contract)
            if self.path == "/healthz":
                # per-model version (checkpoint epoch/step + weights
                # manifest hash — the operator's "what is actually serving"
                # answer) and the readiness flag the rolling-restart gate
                # reads: false exactly while a deploy swap is in flight
                self._reply(
                    200,
                    {
                        "status": "ok",
                        "ready": replica.is_ready(),
                        "models": sorted(replica.engine.models),
                        "versions": replica.engine.versions(),
                        "replica": replica.replica,
                        "batch_sizes": replica.engine.batch_sizes,
                    },
                )
            elif self.path == "/metrics":
                # Prometheus exposition of the live aggregate (dtpu-obs v2):
                # rides the existing frontend server — no extra port, and a
                # scrape reads host state only (zero added device syncs)
                try:
                    self._reply_text(200, replica.metrics_text(), PROM_CONTENT_TYPE)
                except Exception as exc:  # scrape must never hang the socket
                    logger.error(f"serve: /metrics failed: {exc!r}")
                    self._reply_text(500, repr(exc), "text/plain")
            else:
                self._reply(404, {"error": f"no route {self.path}"})

        def do_POST(self):  # noqa: N802
            if self.path not in ("/v1/predict", "/predict"):
                self._reply(404, {"error": f"no route {self.path}"})
                return
            # the client-minted trace id (obs/trace.py); malformed or absent
            # headers get a fresh id — the spans must always have a key
            trace_id = ensure_trace_id(self.headers.get(TRACE_HEADER))
            model = ""  # filled once the body parses; the shed hint's key
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                if isinstance(body, dict):
                    model = str(body.get("model", ""))
                self._reply(200, replica.handle(body, trace_id), trace_id)
            except QueueFullError as exc:
                self._reply(
                    503,
                    {"error": "shed", "detail": str(exc)},
                    trace_id,
                    retry_after_s=replica.batcher.retry_after_s(model),
                )
            except BadRequest as exc:
                self._reply(400, {"error": "bad_request", "detail": str(exc)}, trace_id)
            except (json.JSONDecodeError, UnicodeDecodeError) as exc:
                self._reply(400, {"error": "bad_json", "detail": str(exc)}, trace_id)
            except Exception as exc:  # server-side: 500, never a hung socket
                logger.error(f"serve: request failed: {exc!r}")
                self._reply(500, {"error": "internal", "detail": repr(exc)}, trace_id)

        def log_message(self, fmt, *args):  # access log → logger, not stderr
            logger.debug(f"serve http: {fmt % args}")

    return Handler


def resolve_port() -> int:
    """The replica's frontend port: DTPU_SERVE_PORT env (the agent's
    per-replica handoff) > SERVE.PORT > an ephemeral pick that avoids the
    rendezvous ports in play (the serve half of the port-collision fix)."""
    env_port = os.environ.get("DTPU_SERVE_PORT", "")
    if env_port.isdigit() and int(env_port) > 0:
        return int(env_port)
    if int(cfg.SERVE.PORT) > 0:
        return int(cfg.SERVE.PORT)
    from distribuuuu_tpu.runtime.dist import pick_rendezvous_port, rendezvous_ports_in_play

    return pick_rendezvous_port(exclude=rendezvous_ports_in_play())


def run_http(replica: ServeReplica, stop_event: threading.Event) -> None:
    port = resolve_port()
    server = ThreadingHTTPServer((str(cfg.SERVE.HOST), port), _make_handler(replica))
    replica.announce(server.server_address[1])
    logger.info(
        f"dtpu-serve replica {replica.replica}: serving "
        f"{sorted(replica.engine.models)} on "
        f"http://{cfg.SERVE.HOST}:{server.server_address[1]} "
        f"(ladder {replica.engine.batch_sizes})"
    )
    thread = threading.Thread(target=server.serve_forever, daemon=True, name="dtpu-serve-http")
    thread.start()
    try:
        stop_event.wait()
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5.0)


def run_stdin(replica: ServeReplica) -> None:
    """JSONL mode: request per stdin line, response per stdout line."""
    replica.announce(0)
    logger.info(
        f"dtpu-serve replica {replica.replica}: stdin-JSONL mode, serving "
        f"{sorted(replica.engine.models)}"
    )
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            response = replica.handle(json.loads(line))
        except QueueFullError as exc:
            response = {"error": "shed", "detail": str(exc)}
        except (BadRequest, json.JSONDecodeError) as exc:
            response = {"error": "bad_request", "detail": str(exc)}
        except Exception as exc:  # server-side failure: the http path's 500
            # — one bad dispatch must answer its line and keep the replica
            # serving, never break the one-response-per-line protocol
            logger.error(f"serve: stdin request failed: {exc!r}")
            response = {"error": "internal", "detail": repr(exc)}
        print(json.dumps(response), flush=True)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _model_specs() -> list[ModelSpec]:
    entries = list(cfg.SERVE.MODELS)
    if entries:
        return parse_model_specs(entries)
    if not cfg.MODEL.WEIGHTS:
        raise ValueError(
            "nothing to serve: set SERVE.MODELS ('name=arch@weights') or "
            "MODEL.WEIGHTS for a single-model host"
        )
    return [ModelSpec(name=cfg.MODEL.ARCH, arch=cfg.MODEL.ARCH, weights=cfg.MODEL.WEIGHTS)]


def serve_main(argv: list[str] | None = None) -> int:
    """``dtpu-serve`` / ``python -m distribuuuu_tpu.serve``."""
    load_cfg_fom_args("dtpu-serve: batched inference engine.", argv=argv)
    cfg.freeze()
    from distribuuuu_tpu.runtime import data_mesh, setup_distributed

    if cfg.TRAIN.COMPILE_CACHE:
        from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache

        enable_persistent_cache(cfg.TRAIN.COMPILE_CACHE_DIR or None)
    info = setup_distributed()
    setup_logger(cfg.OUT_DIR, info.process_index)
    mesh = data_mesh(cfg.MESH.DATA)
    replica = ServeReplica(mesh, _model_specs(), cfg.OUT_DIR)

    mode = str(cfg.SERVE.MODE)
    stop = threading.Event()
    stop_signum: list[int] = []

    def _on_signal(signum, frame):
        stop_signum.append(signum)
        stop.set()
        if mode == "stdin":
            # the stdin loop blocks in a readline that Python retries after
            # the handler returns (PEP 475) — only an exception raised HERE
            # interrupts it, so stdin mode exits through SystemExit while
            # http mode keeps the event-driven shutdown
            raise SystemExit(128 + signum)

    try:
        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
    except ValueError:  # not the main thread (embedded/test use)
        pass

    try:
        if mode == "stdin":
            run_stdin(replica)
        elif mode == "http":
            run_http(replica, stop)
        else:
            raise ValueError(f"SERVE.MODE must be http/stdin, got {cfg.SERVE.MODE!r}")
    finally:
        replica.shutdown()
    if stop_signum:
        # preemption semantics, matching the worker taxonomy: the supervisor
        # sees an ordinary preempted replica, not a crash to back off from
        return 128 + stop_signum[0]
    return 0
