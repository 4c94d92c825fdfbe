"""Shape bucketing + warm-start re-tuning for the DSE stack.

Tuned plans are keyed on *exact* shapes, so a service facing arbitrary
user shapes either compile-storms (one full exploration per novel
shape) or falls off the tuned path entirely.  This module adds the
middle path, AnyHLS-style specialization classes with best-effort
background refinement:

  * every concrete extent maps to a **bucket** -- the next value on a
    power-of-two-ish ladder ``{s*2^j, s*3*2^(j-1)}`` floored at the
    dtype's sublane multiple ``s`` (``bucket_extent``).  Two shapes in
    one bucket share a specialization class;
  * each completed exploration records its winning plan in a **bucket
    index** inside the tuning-cache document (keyed by a
    shape-independent *family* signature of the pattern / pipeline), so
    the index rides the existing crash-safe store;
  * a cold shape whose family has tuned buckets is served a
    **warm-start plan** immediately: the nearest bucket's plan, its
    tiles re-fitted onto the cold shape's divisor grid
    (``dse.axis_candidates`` -- the existing ragged-tail machinery) and
    re-priced analytically.  No kernel is lowered, nothing is measured,
    nothing is cached -- the warm plan is a loan;
  * a **background re-tune** (daemon thread, bounded by the
    ``resilience.Policy`` deadline, deduplicated per cache key) runs
    the full exploration for the exact shape and promotes its winner
    into the tuning cache -- but only after the winner **certifies**
    against the oracle (``resilience.certify_*``), regardless of
    ``policy.certify``: an unattended background write demands
    validation.  Once promoted, the next request for that shape is an
    exact cache hit.

``STATS`` counts exact hits / warm starts / misses / promotions for
the serving loop and the benchmark's bucket-hit-rate section;
``drain()`` joins outstanding re-tunes (tests, benchmark epilogue).

Enabled per call via ``Options(bucketing=True)`` (or fleet-wide with
``REPRO_BUCKETING=1`` -- read by ``Options.from_env``); ``dse.explore``
/ ``dse.explore_pipeline`` own the call sites.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import threading
from typing import Callable, Dict, Optional, Tuple

from . import ir, resilience, telemetry

# ---------------------------------------------------------------- buckets


def bucket_extent(n: int, *, sublane: int = 1) -> int:
    """Smallest ladder value >= ``n`` from ``{s*2^j, s*3*2^(j-1)}``
    (``s`` = the dtype sublane multiple): powers of two plus their 1.5x
    midpoints, so consecutive buckets are at most 33% apart and every
    bucket is sublane-aligned.  ``n <= s`` collapses to ``s``."""
    n = max(int(n), 1)
    s = max(int(sublane), 1)
    v = s
    while v < n:
        mid = v + v // 2
        if v % 2 == 0 and mid % s == 0 and mid >= n:
            return mid
        v *= 2
    return v


def _bucket_sig(domains: Dict[str, Tuple[int, ...]]) -> str:
    return ";".join(f"{k}={'x'.join(map(str, v))}"
                    for k, v in sorted(domains.items()))


# ------------------------------------------------------- family signatures


def _device() -> str:
    from . import measure
    return measure.device_kind()


def tile_family(p: ir.Pattern, *, vmem_budget: int, align: int) -> str:
    """Shape-independent identity of a tile exploration: pattern tree
    structure (types, names, domain ranks, dtypes), input tensor ranks
    and dtypes, constraints, device kind.  Deliberately excludes
    extents (that is what buckets vary over) and the calibration
    profile hash (warm starts are heuristic seeds; they must survive
    recalibration)."""
    from . import dse
    parts = tuple((type(q).__name__, q.name, len(q.domain),
                   str(q.dtype), bool(q.strided)) for q in ir.walk(p))
    inputs = tuple((t.name, len(t.shape), str(t.dtype))
                   for t in ir.inputs_of(p))
    raw = repr((dse.MODEL_VERSION, _device(), "tile", parts, inputs,
                int(vmem_budget), int(align)))
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def tile_buckets(p: ir.Pattern, *, align: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Per tileable pattern domain, the bucketed extents (mirrors
    ``dse.tile_space``'s iteration: named, untiled, unstrided)."""
    from . import dse
    out: Dict[str, Tuple[int, ...]] = {}
    for q in ir.walk(p):
        if q.strided or not q.domain or q.name in out:
            continue
        sub = dse.dtype_sublane(q.dtype)
        out[q.name] = tuple(bucket_extent(d, sublane=sub)
                            for d in q.domain)
    return out


def pipeline_family(pipe, *, vmem_budget: int, align: int) -> str:
    """Shape-independent identity of a pipeline exploration: per-stage
    structure in topological order plus wiring, device kind and
    constraints (extent-free analogue of ``dse.pipeline_key``)."""
    from . import dse
    from . import pipeline as plmod
    parts = tuple((s.name, type(s).__name__, str(s.dtype), len(s.shape),
                   len(s.domain)) for s in plmod.topo_stages(pipe))
    edges = tuple(sorted(set(plmod._edges(pipe))))
    raw = repr((dse.MODEL_VERSION, _device(), "pipeline", pipe.name,
                parts, edges, tuple(plmod.output_names(pipe)),
                int(vmem_budget), int(align)))
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def pipeline_buckets(pipe) -> Dict[str, Tuple[int, ...]]:
    from . import dse
    from . import pipeline as plmod
    sub = max(dse.dtype_sublane(s.dtype)
              for s in plmod.topo_stages(pipe))
    return {"extent": (bucket_extent(pipe.shared_extent, sublane=sub),)}


# ------------------------------------------------------------ bucket index


def record(space, plan, tc) -> None:
    """Register ``plan``, explored over ``space`` (a ``dse`` search
    space), as the donor for its bucket (idempotent: an identical
    existing entry skips the disk write; a newer tuned plan for the
    same bucket overwrites -- latest wins).  Only a one-kernel plan is
    a donor: a split pipeline's cut structure is priced for one extent
    and does not transfer."""
    doms = space.bucket_domains()
    if doms and space.one_kernel(plan):
        _put(tc, space.family(), doms, plan, space.kind)


def _put(tc, family: str, doms: Dict[str, Tuple[int, ...]], plan,
         kind: str) -> None:
    sig = _bucket_sig(doms)
    entry = {"kind": kind,
             "domains": {k: list(v) for k, v in doms.items()},
             "plan": plan.to_json()}
    if tc.bucket_entries(family).get(sig) == entry:
        return
    tc.bucket_put(family, sig, entry)


def _nearest(entries: Dict[str, Dict],
             want: Dict[str, Tuple[int, ...]],
             kind: str) -> Optional[Dict]:
    """The compatible entry whose bucket is log-nearest to ``want``
    (exact bucket first, then donors >= on every dim -- shrinking a
    tuned tile onto a smaller shape loses less than growing one)."""
    best = None
    best_rank: Tuple = ()
    for _sig, e in entries.items():
        if e.get("kind") != kind:
            continue
        doms = {k: tuple(v) for k, v in e.get("domains", {}).items()}
        if set(doms) != set(want) or any(
                len(doms[k]) != len(want[k]) for k in want):
            continue
        dist = sum(abs(math.log2(max(a, 1)) - math.log2(max(b, 1)))
                   for k in sorted(want)
                   for a, b in zip(doms[k], want[k]))
        ge = all(a >= b for k in want
                 for a, b in zip(doms[k], want[k]))
        rank = (dist > 0, not ge, dist)
        if best is None or rank < best_rank:
            best, best_rank = e, rank
    return best


# -------------------------------------------------------------- warm start


def warm_start(space, tc):
    """A plan for ``space``'s shape adapted from the nearest tuned
    bucket, or None.

    The donor's tiles are mapped onto the cold shape's own candidate
    grid (``space.refit``: the largest ``axis_candidates`` divisor <=
    the donor tile -- the ragged tail falls out of the divisor
    enumeration), at the donor's buffer depth, re-priced analytically.
    Zero lowering, zero measurement; the plan is flagged
    ``warm_start`` and never persisted."""
    want = space.bucket_domains()
    if not want:
        return None
    entry = _nearest(tc.bucket_entries(space.family()), want, space.kind)
    if entry is None:
        return None
    plan = space.refit(space.plan_cls.from_json(entry["plan"]))
    if plan is None:
        return None
    return dataclasses.replace(
        plan, warm_start=True,
        bucket=_bucket_sig({k: tuple(v) for k, v
                            in entry["domains"].items()}))


# -------------------------------------------------- background re-tuning

STATS: Dict[str, int] = {}
_LOCK = threading.Lock()
_INFLIGHT: set = set()
_THREADS: list = []


def _zero() -> Dict[str, int]:
    return {"exact_hits": 0, "warm_hits": 0, "misses": 0,
            "retunes": 0, "promotions": 0, "retune_failures": 0}


STATS.update(_zero())


def note(kind: str) -> None:
    with _LOCK:
        STATS[kind] = STATS.get(kind, 0) + 1
    # mirror into the unified metrics registry (always on): the BENCH
    # json and serving stats read bucket activity from telemetry
    telemetry.count(f"bucket.{kind}")


def stats() -> Dict[str, int]:
    with _LOCK:
        return dict(STATS)


def snapshot() -> Dict[str, int]:
    """Point-in-time copy of the counters, for per-call deltas: the
    process-wide ``STATS`` survive across serve invocations, so any
    hit rate quoted for *one* call must diff two snapshots
    (``delta``), not read the globals."""
    return stats()


def delta(before: Dict[str, int]) -> Dict[str, int]:
    """Per-key counter growth since ``before`` (a ``snapshot()``)."""
    now = stats()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in set(now) | set(before)}


def delta_hit_rate(d: Dict[str, int]) -> float:
    """``hit_rate`` over one ``delta()`` window; 0.0 on no lookups."""
    served = d.get("exact_hits", 0) + d.get("warm_hits", 0)
    total = served + d.get("misses", 0)
    return served / total if total else 0.0


def hit_rate() -> float:
    """(exact + warm) / all lookups under bucketing; 0.0 when unused."""
    s = stats()
    served = s["exact_hits"] + s["warm_hits"]
    total = served + s["misses"]
    return served / total if total else 0.0


def reset_stats() -> None:
    with _LOCK:
        STATS.clear()
        STATS.update(_zero())


def schedule_retune(tag: str, retune: Callable[[], object], *,
                    certify: Callable[[object], Tuple[bool, str]],
                    promote: Callable[[object], None],
                    policy: resilience.Policy) -> Optional[threading.Thread]:
    """Run ``retune()`` on a daemon thread under the policy deadline;
    ``certify(plan)`` gates ``promote(plan)`` -- an uncertified winner
    is discarded and recorded, never promoted.  Deduplicated on
    ``tag`` (one in-flight re-tune per exact cache key); expected
    failures (deadline, lowering, injected faults) degrade to a
    recorded event, unexpected exceptions from the exploration itself
    are still confined to the worker thread but re-recorded as bugs.
    """
    with _LOCK:
        if tag in _INFLIGHT:
            return None
        _INFLIGHT.add(tag)
        STATS["retunes"] += 1
    telemetry.count("bucket.retunes")

    def worker() -> None:
        # the daemon thread gets its own lane in the exported trace
        # (the span records this thread's name/ident)
        with telemetry.span("buckets.retune", tag=tag) as sp:
            try:
                if policy.timeout_s:
                    plan = resilience.run_with_deadline(
                        retune, policy.timeout_s, label=f"retune:{tag}")
                else:
                    plan = retune()
                ok, reason = certify(plan)
                if not ok:
                    note("retune_failures")
                    sp.set(outcome="certify-failed")
                    resilience.record("retune", "certify-failed", tag,
                                      "discarded", reason)
                    return
                promote(plan)
                note("promotions")
                sp.set(outcome="promoted")
            except resilience.EXPECTED_ERRORS as e:
                note("retune_failures")
                sp.set(outcome="abandoned")
                resilience.record("retune", resilience.classify(e), tag,
                                  "abandoned", str(e))
            finally:
                with _LOCK:
                    _INFLIGHT.discard(tag)

    t = threading.Thread(target=worker, daemon=True,
                         name=f"repro-retune-{tag[:24]}")
    with _LOCK:
        _THREADS.append(t)
    t.start()
    return t


def drain(timeout: float = 60.0) -> None:
    """Join outstanding background re-tunes (tests and the benchmark
    epilogue call this before asserting on promotions)."""
    with _LOCK:
        pending = list(_THREADS)
        _THREADS.clear()
    for t in pending:
        t.join(timeout)
