"""Process-wide host memory management: the out-of-core execution tier.

This package is the HOST-side counterpart of the HBM ResidencyManager
(device/residency.py): one byte ledger every memory-hungry site admits
against (``manager()``), plus the disk-spill machinery (compressed Arrow IPC
spill files, Grace hash partitions, sorted runs) those sites switch to when
the ledger says no.
"""

from .manager import (HostMemoryManager, LedgerBudget, QueryMemoryScope,
                      manager, operator_budget)
from .spill import (SpillFile, SpillPartitions, gc_stale_spills, reset_counters,
                    spill_root)

__all__ = [
    "HostMemoryManager", "LedgerBudget", "QueryMemoryScope", "manager",
    "operator_budget", "SpillFile", "SpillPartitions", "gc_stale_spills",
    "reset_counters", "spill_root",
]
