"""Runtime observability: metrics registry, cost analysis, run journal.

Reference analog: platform/profiler.{h,cc} + device_tracer + tools/timeline.py
gave the reference stack its observability surface; here the TPU-native
reproduction gets the counterpart the whole-program-jit design enables:

- ``metrics``  -- thread-safe Counter/Gauge/Histogram registry (always on,
  in-memory only); ``export`` renders it as JSON or Prometheus text.
- ``cost``     -- XLA ``cost_analysis()`` per compiled step -> FLOPs/bytes
  gauges and achieved MFU against the device peak.
- ``journal``  -- JSON-lines run journal (one event per ``Executor.run``,
  plus recompile/predict events), file sink gated on ``PADDLE_TPU_OBS=1``.
- ``timeline`` -- flight-recorder phase spans (feed-prep/dispatch/fetch per
  step; set-up: building the Program, each compile with JAX's own trace /
  lowering / backend / cache-read events as its children) + the unified
  Chrome-trace/Perfetto exporter.
- ``health``   -- NaN/Inf watchdog over fetches/state, one compiled
  any-nonfinite reduction per step (``PADDLE_TPU_OBS_HEALTH=off|warn|raise``).
- ``memory``   -- device memory_stats()/live-buffer gauges + per-program
  ``memory_analysis()`` peak bytes.
- ``anomaly``  -- rolling median/MAD step-time regression detector.
- ``goodput``  -- wall-clock ledger: productive step time vs named loss
  causes, ``goodput_fraction`` + ``lost_seconds_total{cause}``.
- ``server``   -- opt-in live endpoint (``PADDLE_TPU_OBS_PORT``):
  ``/metrics`` ``/healthz`` ``/goodput`` ``/journal``.
- ``fleet``    -- cross-rank aggregation + straggler detection
  (``PADDLE_TPU_FLEET=gather|scrape``).
- ``slo`` / ``alerts`` -- declarative SLO rules over the registry with
  multi-window multi-burn-rate alerting (``PADDLE_TPU_OBS_SLO=rules.json``;
  journal ``alert`` events, ``alerts_total{rule,severity}``,
  ``alerts_active``, the ``/alerts`` endpoint).
- ``blackbox`` -- post-mortem bundles on terminal failure paths
  (``PADDLE_TPU_OBS_BLACKBOX=<dir>``; triage with ``tools/postmortem.py``).
- ``attribution`` -- IR->HLO cost attribution per compiled program
  (``hlo_op_bytes{category}`` gauges, copy-pair blame feeding PT060,
  ``--emit-hlo`` capture) and the ``hlo_diff`` regression explainer
  (``python -m paddle_tpu.observability.attribution A B``).
- ``lowerings`` -- what the op lowerings of a compiled program chose, as
  labelled metrics added once per compile: a lowering reports through
  ``LowerCtx.report``, and the module's ``FAMILIES`` table is the one list
  of the families, their labels and what each counts.
- ``moe`` -- ``load_stats`` for a fetched expert-load vector.

Render everything with ``python -m tools.obs_report``.
"""
from . import metrics  # noqa: F401
from . import export  # noqa: F401
from . import journal  # noqa: F401
from . import cost  # noqa: F401
from . import timeline  # noqa: F401
from . import health  # noqa: F401
from . import memory  # noqa: F401
from . import anomaly  # noqa: F401
from . import goodput  # noqa: F401
from . import server  # noqa: F401
from . import fleet  # noqa: F401
from .metrics import (REGISTRY, MetricsRegistry, Counter, Gauge,  # noqa: F401
                      Histogram)
from .export import to_json, to_prometheus, parse_prometheus  # noqa: F401
from .journal import (enabled, emit, recent, read_journal,  # noqa: F401
                      current_rank)
from .timeline import (phase, export_chrome_trace,  # noqa: F401
                       validate_trace)
from .goodput import (GoodputReport,  # noqa: F401
                      compute as compute_goodput,
                      compute_live as compute_goodput_live,
                      run_ledger,
                      export as export_goodput)
from .server import (ObsServer,  # noqa: F401
                     start as start_server,
                     stop as stop_server)
from .fleet import FleetMonitor, detect_stragglers  # noqa: F401
from . import attribution  # noqa: F401
from . import moe  # noqa: F401
from . import alerts  # noqa: F401
from . import slo  # noqa: F401
from . import blackbox  # noqa: F401
from .alerts import Alert, AlertManager  # noqa: F401
from .slo import (SLOEngine, SLOConfigError, Rule,  # noqa: F401
                  load_rules, parse_rules, validate_rules,
                  alerts_doc)
from .blackbox import write_bundle  # noqa: F401
from .attribution import (ProgramAttribution,  # noqa: F401
                          attribute_hlo_text, diff_attributions,
                          format_diff)
