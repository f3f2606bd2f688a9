from .checkpoint import CheckpointManager, load_pretrained
from .faults import (Backoff, CorruptRecord, FaultError, FaultSchedule,
                     FaultSpec, Preemption, inject, maybe_fault)
from .health import (HealthMonitor, HealthSpec, NumericDivergence,
                     delta_health, health_probes)
