"""Unified priority-aware dataflow engine.

One memory-budgeted, priority-classed DAG executor for every byte the
library moves: takes (stage → io writes), restores
(fetch → consume reads), and the secondary consumers (scrub, verify,
``Snapshot.gc``, cache populates, swarm/bcast origin fetches) all lower
onto the same task-graph model — nodes are stage/hash/io/verify/consume
steps with byte costs, edges carry the data AND the budget reservation —
executed by :class:`GraphExecutor` under one admission discipline.

Priority classes (``FOREGROUND > NORMAL > BACKGROUND``) preempt at chunk
granularity through the process-wide :class:`QoSArbiter`: a foreground
replica restore arriving mid-drain steals the next admission (budget,
io/hash/transfer-pool slots) rather than waiting for the
drain to finish. See ``docs/performance.md`` ("The dataflow engine").
"""

from .graph import Node, Priority  # noqa: F401
from .executor import (  # noqa: F401
    Budget,
    GraphExecutor,
    NodeContext,
    ProgressReporter,
    run_graph,
)
from .qos import (  # noqa: F401
    QoSArbiter,
    current_priority,
    demand_scope,
    get_arbiter,
    parse_priority,
    pause_point,
    priority_scope,
)
