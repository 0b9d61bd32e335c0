"""repro.runtime — the execution/robustness layer (DESIGN.md §9).

`Executor` schedules oversubscribed logical streams against one big-atomic
target with fault-injected recovery; the watchdog, preemption guard and
elastic resharding it composes are exported alongside.
"""

from repro.runtime.preemption import PreemptionGuard  # noqa: F401
from repro.runtime.stragglers import StragglerPlan, StragglerWatchdog  # noqa: F401
from repro.runtime.elastic import (  # noqa: F401
    MeshPlan, elastic_mesh, mesh_plan, reshard_dist, reshard_state)
from repro.runtime.executor import (  # noqa: F401
    DistTarget, Executor, IssueRec, LocalTarget, Recovery, StreamShed)
from repro.runtime.streams import (  # noqa: F401
    AdmissionStream, DecodeStream, InFlight, McasStream, SyntheticStream,
    serving_streams)
from repro.runtime.faults import (  # noqa: F401
    DATA_KINDS, SCHED_KINDS, Fault, FaultInjector, IssueFault)
