"""Take planning: the preflight collective round and the cross-take plan cache.

Why this exists (the scaling story): a training loop calls ``Snapshot.take``
every N steps with an *identical* state structure, shardings, and world size
— only the values (and the destination path) change. The reference re-pays
the full coordination bill on every take: key all_gather + a barrier per key,
partition all_gather, hostname all_gather, manifest gather (reference
``snapshot.py:354-370,425``; ``partitioner.py:126-144``;
``scheduler.py:45-65``). Each all_gather costs O(world) store reads on
*every* rank, so the per-take stall grows linearly with world size — the
visible threat to a <5 s stall budget at pod scale (v5e-256).

The design here collapses a steady-state take to **constant per-rank store
traffic**:

1. Every rank flattens its local state (no collectives) and hashes a
   *fingerprint* of everything that shapes the plan: logical paths, leaf
   shapes/dtypes/shardings, world size, replicated globs, and the planning
   knobs — but NOT values or the destination path.
2. One **preflight** round — ``gather_object`` to rank 0 + one
   ``broadcast_object`` back (a constant 2 store ops per non-zero rank) —
   carries ``(path, base, globs, plan_token)``. Rank 0 resolves the
   canonical path/base (rank 0 wins, with divergence warnings — reference
   ``snapshot.py:789-826`` semantics), intersects replicated globs, and
   decides HIT iff every rank holds a cached plan for its own (rank-local)
   fingerprint and all plans carry the same take-sequence token — i.e. they
   were computed together by one earlier take.
3. On a HIT the take reuses the cached replicated-write partition assignment
   and the cached local-world-size (so the partition all_gather and the
   hostname all_gather are skipped), and the manifest gather shrinks to a
   per-rank **delta** against the previous take's entries (typically just
   the step counter and other inline primitives).

A rank whose structure changed finds no cached plan under its new
fingerprint and reports ``plan_token=None``; rank 0 broadcasts MISS and
every rank runs the full path — ranks can never diverge on which
collectives they issue, because the decision itself is a collective.

Correctness notes:

- The fingerprint deliberately excludes values: value changes flow through
  the delta manifest gather, which diffs *entry dicts* (so even entries that
  change for reasons outside the fingerprint — e.g. relocated slab paths —
  are re-gathered correctly).
- ``plan_token`` (None when the rank holds no plan) also reflects the local
  knob, so disabling ``TORCHSNAPSHOT_TPU_PLAN_CACHE`` on any one rank
  safely forces a global MISS (never a deadlock).
- World size 1 runs no collectives at all; the cache is bypassed (there is
  nothing to save).
"""

from __future__ import annotations

import hashlib
import logging
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .manifest import Manifest
from .parallel.coordinator import Coordinator
from .utils import knobs

logger = logging.getLogger(__name__)

# Keyset-divergence patterns already surfaced by this process (rank 0).
_WARNED_KEYSET_SIGS: "set" = set()

# Bump when the fingerprint payload or cached-plan layout changes: stale
# in-process caches from an older scheme must never satisfy a new build.
# v3: dedup identities key off the v2 tree-digest root, whose grain
# (TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES) joined the knob signature.
# v4: the fingerprint also keys the PREPARED-state cache (stagers + write
# requests, prepare_cache.py), so every remaining prepare-affecting input
# joined the knob signature: device batching, the async capture mode, and
# the defensive-copy switch.
# v5: the chunk-streamed write path and its three knobs left the signature.
_FINGERPRINT_VERSION = 5

def _leaf_descriptor(value: Any, world_size: int) -> Tuple:
    """Everything about one leaf that shapes the plan — never its values.

    For jax arrays this includes the addressable shard indices, replica ids
    and device ids: the sharded preparer's shard list, the replicated
    classification, and the per-rank write set are all functions of these
    (``io_preparer.classify``, ``io_preparers/sharded_array.py``).
    """
    from .io_preparer import classify

    kind = classify(value, world_size)
    if kind in ("primitive", "object"):
        return (kind, type(value).__name__)
    if isinstance(value, np.ndarray):
        return (kind, value.dtype.str, tuple(value.shape))
    # jax array (sharded / replicated_array / array)
    shards = tuple(
        (
            tuple(
                (s.start, s.stop, s.step) if isinstance(s, slice) else s
                for s in (
                    shard.index
                    if isinstance(shard.index, tuple)
                    else (shard.index,)
                )
            ),
            shard.replica_id,
            shard.device.id,
        )
        for shard in value.addressable_shards
    )
    return (
        kind,
        str(value.dtype),
        tuple(value.shape),
        bool(value.sharding.is_fully_replicated),
        shards,
    )


def compute_fingerprint(
    flattened: Dict[str, Any],
    world_size: int,
    replicated_globs: List[str],
) -> str:
    """Hash of the plan-shaping inputs (structure + shardings + knobs)."""
    knob_sig = (
        knobs.get_max_chunk_size_bytes(),
        knobs.get_max_shard_size_bytes(),
        knobs.get_slab_size_threshold_bytes(),
        knobs.is_batching_enabled(),
        knobs.get_compression(),
        knobs.get_compression_level(),
        knobs.get_compression_frame_bytes(),
        knobs.is_checksums_enabled(),
        # The RAW env string, not the resolved boolean: ``auto`` resolves
        # per-host (CPU count), and identical-env ranks must produce
        # identical fingerprints or heterogeneous hosts would never agree
        # on a plan-cache hit (ADVICE round 5).
        knobs.get_dedup_digests_env(),
        # The tree-digest grain is part of every v2 object's dedup/cache
        # identity (the root is grain-dependent), so a grain change must
        # invalidate cached plans like any other identity-shaping knob.
        # Resolved from env only, so identical-env ranks resolve
        # identically.
        knobs.get_hash_chunk_bytes(),
        # Prepare-affecting inputs the PREPARED-state cache keys on (v4):
        # device batching (slab stager choice), and the capture knobs
        # (whether stagers were built against forked or caller-owned
        # arrays).
        knobs.is_device_batching_enabled(),
        knobs.is_async_device_copy_enabled(),
        knobs.get_async_capture_mode(),
    )
    payload = (
        _FINGERPRINT_VERSION,
        world_size,
        tuple(sorted(set(replicated_globs))),
        knob_sig,
        tuple(
            (path, _leaf_descriptor(value, world_size))
            for path, value in sorted(flattened.items())
        ),
    )
    return hashlib.sha256(repr(payload).encode()).hexdigest()


@dataclass
class CachedPlan:
    """What a cache hit reuses (per fingerprint, per process)."""

    # The take sequence number at which this plan was stored. Takes are SPMD,
    # so the counter advances in lockstep across ranks and "all ranks hold a
    # plan with the SAME token" certifies the plans were computed together —
    # guarding against ranks hitting plans from *different* past takes whose
    # partition assignments don't compose (possible when ranks alternate
    # among several cached structures out of phase).
    token: int
    # Replicated storage path -> writer rank (partitioner output).
    assignment: Dict[str, int]
    # This rank's last take's manifest as {logical_path: entry_dict} — the
    # delta baseline for the next manifest gather.
    local_entry_dicts: Dict[str, dict]
    # Rank 0 only: every rank's last entry dicts (same delta baseline,
    # receiver side). None on other ranks.
    gathered_entry_dicts: Optional[List[Dict[str, dict]]]


@dataclass
class PreflightResult:
    hit: bool
    path: str
    base: Optional[str]
    replicated_globs: List[str]
    # Recorded chain length of the base when it was CATALOG-auto-resolved
    # during this preflight (>= 0; the take's own chain is base+1), or -1
    # for an explicit/absent base. Broadcast with the decision so every
    # rank records the same chain length.
    base_chain_len: int = -1


@dataclass
class TakePlan:
    """Output of the planning stage, consumed by ``Snapshot._take_impl``."""

    path: str
    base: Optional[str]
    replicated_globs: List[str]
    flattened: Dict[str, Any]
    manifest: Manifest  # container entries from flatten()
    rng_states: List[Tuple[str, Any, Any]]
    fingerprint: str
    cache_hit: bool
    cached: Optional[CachedPlan]
    # Phase spans accumulated since planning began (telemetry.PhaseTracker);
    # _take_impl keeps marking phases on the same tracker so the stall
    # decomposition covers planning + impl as one sequence.
    phase_tracker: Any = None
    # See PreflightResult.base_chain_len.
    base_chain_len: int = -1
    # Set by _take_impl when this take acquired (hit) or stored (miss) a
    # prepared-state cache entry (``prepare_cache.PreparedTake``); the
    # pipeline-completion paths release it so the cached stagers drop
    # their array references.
    prepared_entry: Any = None


def get_plan_cache(coord: Coordinator) -> "Dict[str, CachedPlan]":
    """The per-process plan cache, attached to the (long-lived) coordinator
    so tests that build private coordinators get private caches."""
    cache = getattr(coord, "_take_plan_cache", None)
    if cache is None:
        cache = {}
        coord._take_plan_cache = cache  # type: ignore[attr-defined]
    return cache


def probe_plan(coord: Coordinator, fingerprint: str) -> Optional[CachedPlan]:
    """Look up a cached plan AND refresh its recency (dict insertion order is
    the LRU order). Without the refresh, a loop alternating more structures
    than the bound — or a few cold structures passing through — would evict
    the steadily-hit plan and the cache would silently stop helping."""
    cache = get_plan_cache(coord)
    plan = cache.pop(fingerprint, None)
    if plan is not None:
        cache[fingerprint] = plan
    return plan


def store_plan(coord: Coordinator, fingerprint: str, plan: CachedPlan) -> None:
    """Insert/refresh a plan; bound per knobs.get_plan_cache_size (LRU —
    insertion order IS the recency order, maintained here and by
    probe_plan)."""
    cache = get_plan_cache(coord)
    cache.pop(fingerprint, None)
    cache[fingerprint] = plan
    bound = knobs.get_plan_cache_size()
    while len(cache) > bound:
        cache.pop(next(iter(cache)))


def preflight(
    coord: Coordinator,
    path: str,
    base: Optional[str],
    replicated_globs: List[str],
    plan_token: Optional[int],
    keys_sig: Optional[str] = None,
) -> PreflightResult:
    """One gather + one broadcast replacing the per-take path/glob/base/key
    all_gathers and deciding hit/miss globally (see module docstring).

    ``plan_token`` is the rank's cached plan's take-sequence token (None if
    it holds no plan for its local fingerprint). The fingerprint itself is
    deliberately rank-LOCAL — sharded arrays give every rank different
    addressable shards, so fingerprints legitimately differ across ranks —
    and never crosses the wire; hit requires every rank to hold a plan and
    all tokens to match (i.e. all plans were computed by the same take).

    ``keys_sig`` (a checksum of this rank's top-level app-state keys) rides
    the same gather so rank 0 can surface asymmetric keysets: per-rank-only
    statefuls are legal, but one whose ``state_dict()`` issues coordinator
    collectives desyncs the collective generation counters on the ranks
    that skip it — a later hang with no diagnostic (ADVICE round 3,
    item 4). Diagnosis only; never changes the decision.
    """
    globs_local = sorted(set(replicated_globs))
    if coord.get_world_size() == 1:
        base, base_chain = _resolve_base(base, path)
        return PreflightResult(
            hit=False,
            path=path,
            base=base,
            replicated_globs=globs_local,
            base_chain_len=base_chain,
        )
    gathered = coord.gather_object(
        (path, base, globs_local, plan_token, keys_sig), dst=0
    )
    decision: Optional[Tuple[bool, str, Optional[str], List[str], int]] = None
    if gathered is not None:  # rank 0
        paths = [g[0] for g in gathered]
        bases = [g[1] for g in gathered]
        globs = [g[2] for g in gathered]
        tokens = [g[3] for g in gathered]
        keys_sigs = [g[4] for g in gathered]
        sig_set = frozenset(keys_sigs)
        if len(sig_set) > 1 and sig_set not in _WARNED_KEYSET_SIGS:
            # Once per distinct divergence pattern: a legal per-rank
            # stateful would otherwise log every take for the whole run.
            _WARNED_KEYSET_SIGS.add(sig_set)
            logger.warning(
                "Rank-divergent app_state keysets (key checksums %s). "
                "Per-rank-only statefuls are fine, but any stateful whose "
                "state_dict()/load_state_dict() issues collectives must be "
                "present on EVERY rank, or the ranks that skip it will "
                "desynchronize and a later collective will hang.",
                keys_sigs,
            )
        if any(p != paths[0] for p in paths):
            logger.warning(
                "Rank-divergent snapshot paths %s; using rank 0's: %s",
                paths,
                paths[0],
            )
        if any(b != bases[0] for b in bases):
            logger.warning(
                "Rank-divergent base snapshots %s; using rank 0's: %s",
                bases,
                bases[0],
            )
        common: Set[str] = set(globs[0])
        for g in globs[1:]:
            common &= set(g)
        dropped = set().union(*map(set, globs)) - common
        if dropped:
            logger.warning(
                "Ignoring rank-asymmetric replicated globs: %s", dropped
            )
        hit = tokens[0] is not None and all(t == tokens[0] for t in tokens)
        # Catalog auto-base resolution happens HERE, on rank 0 only: one
        # catalog reader per take (steady-state hits the per-process chain
        # cache and does no storage I/O), and the RESOLVED base + its
        # recorded chain length ride the decision broadcast below — every
        # rank agrees on the base by construction, with no per-rank
        # catalog reads to race against a concurrent commit.
        base0, base_chain = _resolve_base(bases[0], paths[0])
        decision = (hit, paths[0], base0, sorted(common), base_chain)
    # Broadcast OUTSIDE the rank-0 block above: the decision collective
    # must be issued by every rank (src posts, sinks read) — keeping it
    # under the `gathered is not None` branch would be exactly the TSA901
    # rank-conditional-collective hazard the analyzer now gates.
    decision = coord.broadcast_object(decision, src=0)
    hit, canonical_path, canonical_base, common_globs, base_chain = decision
    return PreflightResult(
        hit=hit,
        path=canonical_path,
        base=canonical_base,
        replicated_globs=common_globs,
        base_chain_len=base_chain,
    )


def _resolve_base(
    base: Optional[str], path: str
) -> Tuple[Optional[str], int]:
    """Resolve a catalog auto-base sentinel (``Snapshot.take(job=...)``)
    into a real base path + its recorded chain length; explicit/absent
    bases pass through with chain -1 (unknown). Local storage I/O only —
    no collectives (the caller broadcasts the result)."""
    from . import catalog as catalog_mod

    if base is None or not catalog_mod.is_auto_base(base):
        return base, -1
    resolved, chain = catalog_mod.resolve_auto_base(base, path)
    return resolved, (chain if resolved is not None else 0)


def gather_manifest_delta(
    manifest: Manifest,
    coord: Coordinator,
    cached: CachedPlan,
) -> Optional[Manifest]:
    """Cache-hit replacement for the full manifest gather: each rank sends
    only the entries whose serialized dict changed since the previous take
    (plus any paths that vanished — defensive; the fingerprint should make
    that impossible). Returns the global manifest on rank 0, None elsewhere.

    Updates ``cached`` in place on every rank so the next take diffs against
    this one.
    """
    from .manifest import entry_from_dict, entry_to_dict
    from .partitioner import consolidate_replicated_entries

    local = {p: entry_to_dict(e) for p, e in manifest.items()}
    delta = {
        p: d for p, d in local.items() if cached.local_entry_dicts.get(p) != d
    }
    removed = [p for p in cached.local_entry_dicts if p not in local]
    gathered = coord.gather_object((delta, removed), dst=0)
    cached.local_entry_dicts = local
    if gathered is None:
        return None
    assert cached.gathered_entry_dicts is not None
    new_gathered: List[Dict[str, dict]] = []
    for r, (dlt, dels) in enumerate(gathered):
        merged = dict(cached.gathered_entry_dicts[r])
        merged.update(dlt)
        for p in dels:
            merged.pop(p, None)
        new_gathered.append(merged)
    cached.gathered_entry_dicts = new_gathered
    global_manifest: Manifest = {
        f"{r}/{p}": entry_from_dict(d)
        for r, m in enumerate(new_gathered)
        for p, d in m.items()
    }
    consolidate_replicated_entries(global_manifest)
    return global_manifest
