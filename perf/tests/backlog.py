"""The per-layer quantities every backlog cell reports, under the names
``BENCHMARK.json`` has for them: one entry a quantity, the six cells in its
``workloads`` list (PR 45).  A family's test adds its own to this SET; where
an entry lies in the list, and how many there are, no test holds."""

SHARED = frozenset({
    "engine.step_ms.backlog", "engine.chunk_step_ms.backlog",
    "engine.admit_ms.backlog", "engine.admit_rows.backlog",
    "engine.chunk_rows.backlog", "engine.occupancy",
    "engine.prefill_real_share.backlog", "device.idle_share.backlog",
    "window.compiles.backlog", "window.stall_ms.backlog", "xla.compile_s",
    "xla.cache_misses"})
# no TPU plane in a CPU's trace: the reader finds nothing and the metric is
# left out of the line
NOT_ON_A_CPU = frozenset({"device.idle_share.backlog"})
