from repro.distributed import sharding
from repro.distributed.fault import (CapacityEvent, FaultInjector, degrade,
                                     rebalance)

__all__ = ["sharding", "CapacityEvent", "FaultInjector", "degrade",
           "rebalance"]
