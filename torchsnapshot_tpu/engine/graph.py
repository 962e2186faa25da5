"""The task-graph model the dataflow engine executes.

A graph is a set of :class:`Node` chains. Each node is one unit of work —
a ``stage`` (D2H + serialize), ``hash``, ``io`` (storage write/read),
``verify``, ``consume`` (deserialize + scatter), or ``delete`` — with a
byte cost and a thread/slot pool. Edges
(``successor``) carry both the data handoff (the predecessor's result
becomes the successor's payload) and the *budget* handoff: the
reservation debited when the predecessor was admitted travels along the
edge and is credited back only when the edge's final node completes (or
the graph aborts). Stage→io buffers and fetch→consume reads both reduce
to that one rule.

Both execution paths lower onto this model:

- writes: ``stage`` node (cost = staging estimate, re-costed to the
  actual buffer on completion) → ``io`` node (hash + dedup + write);
- reads: ``read_io`` node (fetch + digest verify, cost = consuming cost) →
  ``consume`` node.

Secondary consumers (scrub, ``Snapshot.gc``, verify) build flat graphs of
``verify``/``delete`` nodes at BACKGROUND priority, so one ledger-audited
budget discipline governs every byte any part of the library holds in
flight.
"""

from __future__ import annotations

from typing import Any, Awaitable, Callable, List, Optional

from .qos import Priority  # noqa: F401 - re-exported as part of the model

# A node body: ``async def body(ctx, payload)``. ``payload`` is the
# predecessor's result (None for root nodes); ``ctx`` is the engine's
# NodeContext (recost/note_bytes).
NodeBody = Callable[[Any, Any], Awaitable[Any]]


class Node:
    """One step of a task graph. See the module docstring for the model."""

    __slots__ = (
        "kind",
        "run",
        "cost_bytes",
        "pool",
        "stream",
        "path",
        "deferred",
        "successor",
    )

    def __init__(
        self,
        kind: str,
        run: NodeBody,
        *,
        cost_bytes: int = 0,
        pool: str = "io",
        stream: Optional[str] = None,
        path: str = "",
        deferred: bool = False,
        successor: Optional["Node"] = None,
    ) -> None:
        self.kind = kind  # span suffix: <span_prefix>.<kind>
        self.run = run
        self.cost_bytes = cost_bytes  # admission reservation (bytes)
        self.pool = pool  # slot pool ("staging"/"io"/"consume")
        self.stream = stream  # interval stream the execution joins, or None
        self.path = path  # telemetry attribution
        self.deferred = deferred  # inadmissible until release_deferred()
        self.successor = successor  # data+budget handoff edge

    def then(self, node: "Node") -> "Node":
        """Chain ``node`` after this one (the data+budget handoff edge) and
        return it, so builders can write ``graph.add(a.then(b))``-style
        chains."""
        self.successor = node
        return node

    def chain(self) -> List["Node"]:
        out: List[Node] = [self]
        node = self.successor
        while node is not None:
            out.append(node)
            node = node.successor
        return out
