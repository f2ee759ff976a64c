"""`dtpu-obs`: structured telemetry for distribuuuu-tpu (docs/OBSERVABILITY.md).

The observable surface of the framework, in one subsystem:

- **Metrics journal** (`obs.journal`): crash-safe rank-0 JSONL, one typed
  record per PRINT_FREQ window / epoch / eval / checkpoint / fault event,
  schema-validated.
- **Telemetry core** (`obs.telemetry`): the `Telemetry` handle the trainer,
  checkpointing, data loader and resilience layer all report through;
  `current()` is a no-op outside a run so instrumentation is unconditional.
- **Counters** (`obs.monitors`): `jax.monitoring` backend-compile/cache
  events bridged into per-epoch journal records.
- **MFU/goodput** (`obs.flops` + telemetry): XLA-cost-model FLOPs per step
  (priced by *lowering* — no extra compile) against the hardware peak, and
  productive-time ÷ elapsed goodput.
- **Profiler windows** (`obs.profiler` + `obs.traceparse`): config- and
  SIGUSR1-driven `jax.profiler` captures with the per-op device-time table
  journaled.
- **Live telemetry plane** (dtpu-obs v2): incremental journal tailing +
  current-state aggregation (`obs.stream`), Prometheus ``/metrics``
  exporters + the embeddable `ObsPlane` (`obs.exporter`), request/step
  tracing (`obs.trace`: serve request spans; the train loop's phases
  ``h2d_transfer`` / ``data_wait`` / ``throttle`` / ``dispatch`` /
  ``fetch_wait`` / ``checkpoint`` as profiler spans and wait counters, the
  jitted step's ``dtpu.grad_sync`` / ``optimizer`` / ``guard`` /
  ``metrics`` / ``loss`` scopes, and per-window ``data_wait`` /
  ``throttle`` / ``dispatch`` / ``fetch_wait`` / ``host`` journal spans),
  and the declarative alarm engine (`obs.alarms`).
- **CLI** (`obs.__main__`): ``python -m distribuuuu_tpu.obs
  summarize|validate|export``.
"""

from distribuuuu_tpu.obs.alarms import (  # noqa: F401
    AlarmEngine,
    AlarmRule,
    parse_alarm_rules,
)
from distribuuuu_tpu.obs.exporter import (  # noqa: F401
    MetricsServer,
    ObsPlane,
    render_prometheus,
)
from distribuuuu_tpu.obs.journal import (  # noqa: F401
    WINDOW_COUNTERS,
    Journal,
    read_journal,
    validate_journal,
    validate_record,
)
from distribuuuu_tpu.obs.memory import activation_bytes, state_bytes  # noqa: F401
from distribuuuu_tpu.obs.monitors import MonitoringBridge  # noqa: F401
from distribuuuu_tpu.obs.profiler import (  # noqa: F401
    ProfilerWindows,
    install_sigusr1_handler,
    request_profile,
)
from distribuuuu_tpu.obs.stream import (  # noqa: F401
    JournalTailer,
    LiveAggregator,
)
from distribuuuu_tpu.obs.telemetry import (  # noqa: F401
    NullTelemetry,
    Telemetry,
    current,
    end_run,
    journal_path,
    set_current,
    start_run,
)
