"""Continuous-checkpointing benchmark: sustained delta chains at bounded
bucket growth, plus the chain-aware warm restore.

The write-side mirror of ``benchmarks/serving``: one job snapshots every
"step" into one bucket via catalog-managed delta chains
(``Snapshot.take(job=...)`` auto-selects each take's ``base=`` and rebases
to a full snapshot at ``max_chain_len``), a keep-last-K retention policy
runs every ``RETAIN_EVERY`` steps, and the harness asserts the two
production claims end to end:

1. **Bounded growth** — with retention on, bucket bytes PLATEAU as snapshot
   count grows without bound (keep-last-K ⇒ steady-state size ≈ the live
   window, not the history). Bytes are measured inode-deduped (fs hard
   links are the dedup substrate: N chain members sharing a frozen object
   cost its bytes once).

2. **Chain-aware warm restore** — a replica that restored step T-1 with the
   content-addressed read cache on restores step T reading ≈ only that
   delta's NEW bytes from origin: chain-shared objects hit the digest-keyed
   cache (one entry per content across the chain), so origin traffic is the
   adapter delta, not the full state.

Also reported: sustained checkpoints/minute, per-step wall times, chain
shape (rebase cadence), and the bucket-bytes-vs-snapshot-count series.

  python benchmarks/continuous/main.py            # acceptance scale (50+)
  CONTINUOUS_BENCH_STEPS=8 ... main.py            # smoke scale (tier-1)

Env knobs: CONTINUOUS_BENCH_STEPS (default 60), CONTINUOUS_BENCH_KEEP_LAST
(5), CONTINUOUS_BENCH_RETAIN_EVERY (5), CONTINUOUS_BENCH_MAX_CHAIN (8),
CONTINUOUS_BENCH_FROZEN_MB (32), CONTINUOUS_BENCH_ADAPTER_MB (2).
CONTINUOUS_BENCH_EXPECT_ANOMALY selects the health-detector contract:
unset/"" asserts zero anomalies on the clean run (no false positives);
"stall" asserts a stall_spike IS detected. CONTINUOUS_BENCH_FAULT_STEP
(default: 3/4 through the run when EXPECT_ANOMALY=stall) picks the step
whose take runs under CONTINUOUS_BENCH_FAULT_SPEC (a faults.py spec,
default a 1.5s write stall) — fault-rule state lives per plugin instance
(one per take), so an env-level spec would stall EVERY step and never
spike against its own trailing median; the harness scopes the knob to
the one step instead.
The last JSON line on stdout is the machine-readable result.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import (  # noqa: E402
    maybe_init_distributed,
    start_host_only_run,
)


def bucket_bytes(root: str) -> int:
    """Bytes the bucket actually occupies, hard-link (inode) deduped —
    the number retention must bound."""
    seen = set()
    total = 0
    for dirpath, _dirnames, filenames in os.walk(root):
        for fname in filenames:
            try:
                st = os.stat(os.path.join(dirpath, fname))
            except OSError:
                continue
            key = (st.st_dev, st.st_ino)
            if key not in seen:
                seen.add(key)
                total += st.st_size
    return total


def main() -> None:
    # Dedup digests must be pinned on: the auto default disables them on
    # single-vCPU hosts and the whole chain story silently degrades to
    # full rewrites (same rationale as benchmarks/incremental).
    os.environ["TORCHSNAPSHOT_TPU_DEDUP_DIGESTS"] = "1"
    host_only = start_host_only_run("continuous")
    maybe_init_distributed()

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import catalog
    from torchsnapshot_tpu import snapshot as snapshot_mod
    from torchsnapshot_tpu.telemetry import health, steprecord
    from torchsnapshot_tpu.utils import knobs

    steps = int(os.environ.get("CONTINUOUS_BENCH_STEPS", "60"))
    keep_last = int(os.environ.get("CONTINUOUS_BENCH_KEEP_LAST", "5"))
    retain_every = int(os.environ.get("CONTINUOUS_BENCH_RETAIN_EVERY", "5"))
    max_chain = int(os.environ.get("CONTINUOUS_BENCH_MAX_CHAIN", "8"))
    frozen_mb = float(os.environ.get("CONTINUOUS_BENCH_FROZEN_MB", "32"))
    adapter_mb = float(os.environ.get("CONTINUOUS_BENCH_ADAPTER_MB", "2"))
    expect = os.environ.get("CONTINUOUS_BENCH_EXPECT_ANOMALY", "")
    fault_step = int(
        os.environ.get(
            "CONTINUOUS_BENCH_FAULT_STEP",
            str(steps * 3 // 4) if expect == "stall" else "-1",
        )
    )
    fault_spec = os.environ.get(
        "CONTINUOUS_BENCH_FAULT_SPEC", "op=write,kind=stall,secs=1.5,at=0"
    )

    rng = np.random.default_rng(0)
    n_frozen = max(1, int(frozen_mb * 1e6 / (4 * 1024 * 1024)))
    frozen = {
        f"backbone{i}": rng.standard_normal(1024 * 1024).astype(np.float32)
        for i in range(n_frozen)
    }
    n_adapt = max(1, int(adapter_mb * 1e6 / (256 * 1024)))
    adapters = {
        f"lora{i}": rng.standard_normal(64 * 1024).astype(np.float32)
        for i in range(n_adapt)
    }
    frozen_bytes = sum(a.nbytes for a in frozen.values())
    adapter_bytes = sum(a.nbytes for a in adapters.values())

    root = tempfile.mkdtemp(prefix="tss_continuous_")
    bucket = os.path.join(root, "bucket")
    os.makedirs(bucket)
    cache_dir = os.path.join(root, "cache")
    policy = catalog.RetentionPolicy.parse(f"last={keep_last}")

    take_walls = []
    size_series = []  # (snapshot_count_taken, bucket_bytes)
    # Job-lifetime step-telemetry series. Retention GC deletes a condemned
    # snapshot's step record along with it, so the catalog only ever holds
    # the live window — the bench accumulates the full series by syncing
    # BEFORE each retention pass (and once after the loop).
    step_series = []
    seen_steps = set()

    def sync_step_series():
        try:
            with catalog.Catalog(bucket) as cat:
                for rec in cat.load_step_telemetry(job="continuous-bench"):
                    if rec.get("step") not in seen_steps:
                        seen_steps.add(rec.get("step"))
                        step_series.append(rec)
        except Exception:  # noqa: BLE001 - telemetry is fail-open
            pass

    t_begin = time.perf_counter()
    try:
        for step in range(steps):
            # "Train": only the adapters change between checkpoints.
            for k in adapters:
                adapters[k] = adapters[k] + 1.0
            app = {"m": StateDict(**frozen, **adapters)}
            saved_faults = os.environ.get("TORCHSNAPSHOT_TPU_FAULTS")
            if step == fault_step:
                os.environ["TORCHSNAPSHOT_TPU_FAULTS"] = fault_spec
            t0 = time.perf_counter()
            try:
                Snapshot.take(
                    os.path.join(bucket, f"step_{step:05d}"),
                    app,
                    job="continuous-bench",
                    step=step,
                    max_chain_len=max_chain,
                )
            finally:
                if step == fault_step:
                    if saved_faults is None:
                        os.environ.pop("TORCHSNAPSHOT_TPU_FAULTS", None)
                    else:
                        os.environ["TORCHSNAPSHOT_TPU_FAULTS"] = saved_faults
            take_walls.append(time.perf_counter() - t0)
            if (step + 1) % retain_every == 0:
                sync_step_series()
                catalog.retain(bucket, policy, dry_run=False)
            size_series.append((step + 1, bucket_bytes(bucket)))
        sync_step_series()
        step_series.sort(key=lambda r: r.get("step", 0))
        sustained_s = time.perf_counter() - t_begin
        per_minute = steps / sustained_s * 60.0

        with catalog.Catalog(bucket) as cat:
            records = cat.load(job="continuous-bench")
        full_takes = sum(1 for r in records if r.chain_len == 0)
        max_chain_seen = max((r.chain_len for r in records), default=0)

        # Plateau check: once retention has cycled at least twice, the
        # bucket must stop growing with snapshot count. Compare the max of
        # the last quarter against the size right after the SECOND
        # retention pass (the first steady-state point).
        anchor_idx = min(2 * retain_every, len(size_series) - 1)
        anchor = size_series[anchor_idx][1]
        tail = [b for _n, b in size_series[-max(1, len(size_series) // 4):]]
        plateau_ratio = max(tail) / anchor if anchor else float("inf")
        # The retained window itself (worst case: keep_last full snapshots
        # + the in-window deltas) bounds what the bucket may hold.
        window_bound = keep_last * (frozen_bytes + adapter_bytes) * 1.5

        # ---- chain-aware warm restore: restore T-1 cache-warm, then T.
        latest = records[-1].name
        prev = records[-2].name if len(records) > 1 else latest
        os.environ["TORCHSNAPSHOT_TPU_READ_CACHE_DIR"] = cache_dir
        try:
            def restore(name):
                out = {
                    "m": StateDict(
                        **{k: np.zeros_like(v) for k, v in frozen.items()},
                        **{k: np.zeros_like(v) for k, v in adapters.items()},
                    )
                }
                Snapshot(os.path.join(bucket, name)).restore(out)
                return out, dict(snapshot_mod.LAST_RESTORE_STATS)

            _w, warmup_stats = restore(prev)  # populates the cache
            out, warm_stats = restore(latest)
        finally:
            del os.environ["TORCHSNAPSHOT_TPU_READ_CACHE_DIR"]
        warm_origin = warm_stats["attribution"]["origin_bytes"]
        warm_cache = warm_stats["attribution"]["cache_bytes"]
        # The newest step's NEW bytes are its adapters (the frozen
        # backbone dedups along the chain and must come from the cache).
        delta_budget = adapter_bytes * 1.2 + 1e6
        bit_exact = all(
            np.array_equal(out["m"][k], adapters[k]) for k in adapters
        ) and all(np.array_equal(out["m"][k], frozen[k]) for k in frozen)

        # ---- health detectors over the job-lifetime step series.
        anomalies = health.detect_anomalies(
            step_series,
            bucket_bytes=[b for _n, b in size_series],
            window_bound=int(window_bound),
        )
        health.log_anomalies(anomalies)
        timeline = health.render_timeline(step_series, anomalies)
        for line in timeline:
            print(line, file=sys.stderr)

        result = {
            "metric": "sustained_checkpoints_per_minute",
            "value": round(per_minute, 2),
            "unit": "snapshots/min",
            "detail": {
                "steps": steps,
                "keep_last": keep_last,
                "retain_every": retain_every,
                "max_chain_len": max_chain,
                "frozen_mb": round(frozen_bytes / 1e6, 2),
                "adapter_mb": round(adapter_bytes / 1e6, 2),
                "sustained_wall_s": round(sustained_s, 2),
                "take_wall_p50_s": round(sorted(take_walls)[len(take_walls) // 2], 4),
                "take_wall_max_s": round(max(take_walls), 4),
                "bucket_bytes_series": size_series,
                "bucket_bytes_final": size_series[-1][1],
                "bucket_bytes_anchor": anchor,
                "plateau_ratio": round(plateau_ratio, 3),
                "window_bound_bytes": int(window_bound),
                "records_live": len(records),
                "full_takes_live": full_takes,
                "max_chain_seen": max_chain_seen,
                "step_telemetry": {
                    "expect_anomaly": expect,
                    "fault_step": fault_step,
                    "steps_recorded": len(step_series),
                    "summary": steprecord.summarize_series(step_series),
                    "anomalies": anomalies,
                    "timeline": timeline,
                },
                "warm_restore": {
                    "origin_bytes": int(warm_origin),
                    "cache_bytes": int(warm_cache),
                    "delta_budget_bytes": int(delta_budget),
                    "warmup_origin_bytes": int(
                        warmup_stats["attribution"]["origin_bytes"]
                    ),
                    "bit_exact": bool(bit_exact),
                },
            },
        }

        problems = []
        if steps >= 2 * retain_every and plateau_ratio > 1.25:
            problems.append(
                f"bucket did not plateau: ratio {plateau_ratio:.2f} > 1.25"
            )
        if size_series[-1][1] > window_bound:
            problems.append(
                f"bucket {size_series[-1][1]} exceeds the retained-window "
                f"bound {int(window_bound)}"
            )
        if warm_origin > delta_budget:
            problems.append(
                f"warm restore read {warm_origin} origin bytes > delta "
                f"budget {int(delta_budget)} (chain-aware cache not engaged)"
            )
        if not bit_exact:
            problems.append("warm restore not bit-exact")
        if max_chain_seen > max_chain:
            problems.append(
                f"recorded chain {max_chain_seen} exceeds max_chain_len "
                f"{max_chain}"
            )
        telemetry_on = (
            knobs.is_step_telemetry_enabled()
            and knobs.is_telemetry_artifacts_enabled()
        )
        if telemetry_on and len(step_series) < steps:
            problems.append(
                f"step telemetry recorded {len(step_series)}/{steps} steps "
                "(rollup append is dropping records)"
            )
        kinds = sorted({a["kind"] for a in anomalies})
        if expect == "stall":
            if "stall_spike" not in kinds:
                problems.append(
                    "expected a stall_spike anomaly (injected fault) but "
                    f"detectors saw {kinds or 'none'}"
                )
        elif telemetry_on and anomalies:
            problems.append(
                f"false-positive anomalies on clean run: {kinds}"
            )
        result["detail"]["problems"] = problems
        result["device"] = host_only
        print(json.dumps(result))
        if problems:
            print(f"FAILED: {problems}", file=sys.stderr)
            sys.exit(1)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
