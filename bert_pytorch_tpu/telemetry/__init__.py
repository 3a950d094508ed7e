"""Unified training telemetry (docs/telemetry.md).

Step-time decomposition with device-sync discipline (step_timer), bounded
``jax.profiler`` trace windows (profiler), compile/cache observability
(compile_events), failure sentinels + heartbeat (sentinels), and the
versioned JSONL record schema (schema). ``TrainTelemetry`` (runner) is the
facade every training entry point threads its loop through.
"""

from bert_pytorch_tpu.telemetry.cli import (add_cli_args,
                                            default_jsonl_path,
                                            from_args,
                                            stats_every)
from bert_pytorch_tpu.telemetry.collector import (FleetCollector,
                                                  JsonlTailer,
                                                  Target)
from bert_pytorch_tpu.telemetry.flightrec import (FlightRecorder,
                                                  read_postmortem)
from bert_pytorch_tpu.telemetry.introspect import (IntrospectionHub,
                                                   make_debug_server,
                                                   start_debug_server)
from bert_pytorch_tpu.telemetry.compile_events import (CompileMonitor,
                                                       shapes_digest)
from bert_pytorch_tpu.telemetry.memory import (MemorySampler,
                                               analyze_executable)
from bert_pytorch_tpu.telemetry.model_stats import (DivergenceError,
                                                    DivergenceMonitor,
                                                    finetune_grad_health,
                                                    gated_grad_health,
                                                    grad_health)
from bert_pytorch_tpu.telemetry.profiler import (SPANS, ProfilerWindow,
                                                 parse_profile_spec, span,
                                                 startup_open)
from bert_pytorch_tpu.telemetry.runner import TrainTelemetry
from bert_pytorch_tpu.telemetry.schema import (SCHEMA_VERSION,
                                               validate_file,
                                               validate_record)
from bert_pytorch_tpu.telemetry.sentinels import (FailureSentinel, Heartbeat,
                                                  NonFiniteError)
from bert_pytorch_tpu.telemetry.step_timer import StepTimer

__all__ = [
    "CompileMonitor",
    "DivergenceError",
    "DivergenceMonitor",
    "FleetCollector",
    "FlightRecorder",
    "IntrospectionHub",
    "JsonlTailer",
    "MemorySampler",
    "Target",
    "make_debug_server",
    "read_postmortem",
    "start_debug_server",
    "add_cli_args",
    "analyze_executable",
    "default_jsonl_path",
    "from_args",
    "FailureSentinel",
    "finetune_grad_health",
    "gated_grad_health",
    "grad_health",
    "Heartbeat",
    "NonFiniteError",
    "ProfilerWindow",
    "SCHEMA_VERSION",
    "SPANS",
    "StepTimer",
    "stats_every",
    "TrainTelemetry",
    "parse_profile_spec",
    "shapes_digest",
    "span",
    "startup_open",
    "validate_file",
    "validate_record",
]
