"""repro: SPTLB hierarchical multi-objective scheduling of stream-processing apps.

The curated public surface.  Everything an integrator needs for the three
supported workflows imports from here:

* **One-shot balancing** — build a cluster (``generate_cluster`` or
  ``streams.build_cluster``), hand it to ``Sptlb`` and call ``balance``.
* **Closed-loop control** — wrap the cluster in a ``BalanceController``
  and drive it with ``step(TickInput(...)) -> TickResult``.
* **Streaming service** — wrap the controller in a ``ServiceLoop`` and
  ``submit`` typed ``ServiceEvent`` records; the loop decides per tick
  whether drift justifies a delta solve or a full cooperate pass.

Scenario-driven evaluation (``get_scenario`` / ``run_pair`` /
``run_service_pair``) lives in ``repro.sim`` and is re-exported here.
Deeper modules (``repro.core.*``, ``repro.shard``, ``repro.streams``)
remain importable but are not part of the stability contract.
"""
from repro.core import (Advisory, BalanceController, BalanceDecision,
                        ClusterState, ControllerConfig, CoopConfig,
                        FaultToleranceConfig, Mode, Problem, Sptlb,
                        TickInput, TickResult, generate_cluster,
                        make_problem, utilization_fraction)
from repro.service import (AdvisoryBatch, AppArrival, AppDeparture,
                           CapacityUpdate, DriftConfig, DriftDetector,
                           FaultSignal, FleetShadow, LatencyDelta,
                           ServiceConfig, ServiceEvent, ServiceLoop,
                           ServiceStepResult, TelemetryDelta)
from repro.sim import (Scenario, get_scenario, list_scenarios,
                       netlat_compare, run_netlat_pair, run_pair,
                       run_scenario, run_scenario_service, run_service_pair,
                       service_compare)
from repro.streams import PodSlice, StreamApp, StreamRouter, build_cluster

__version__ = "0.1.0"

__all__ = [
    # one-shot balancing
    "Sptlb", "BalanceDecision", "CoopConfig", "Problem", "make_problem",
    "ClusterState", "generate_cluster", "utilization_fraction",
    # closed-loop control
    "BalanceController", "ControllerConfig", "FaultToleranceConfig",
    "Mode", "Advisory", "TickInput", "TickResult",
    # streaming service
    "ServiceLoop", "ServiceConfig", "ServiceStepResult", "ServiceEvent",
    "TelemetryDelta", "CapacityUpdate", "LatencyDelta", "AppArrival",
    "AppDeparture", "AdvisoryBatch", "FaultSignal", "DriftConfig",
    "DriftDetector", "FleetShadow",
    # scenario registry + trajectory evaluation
    "Scenario", "get_scenario", "list_scenarios", "run_pair",
    "run_scenario", "run_scenario_service", "run_service_pair",
    "service_compare", "run_netlat_pair", "netlat_compare",
    # stream-runtime frontend
    "StreamApp", "StreamRouter", "PodSlice", "build_cluster",
    "__version__",
]
