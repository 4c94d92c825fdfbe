"""Pattern-generic tile-size design space exploration (paper §4).

    "In future work, tile sizes for all pattern dimensions will instead
     be determined by the compiler through automated tile size selection
     using modeling and design space exploration."  (paper, §4)

This module is that subsystem, generalized beyond the GEMM template
(``repro.kernels.autotile`` is now a thin front-end over it).  Given any
*untiled* pattern program it:

  1. enumerates MXU/lane-aligned tile-size candidates for every named
     pattern domain (``tile_space``);
  2. applies the full tiling pipeline (``core.strip_mine.tile``) to each
     candidate and prices the tiled IR with the analytic cost model:
     main-memory traffic (``core.cost.traffic``) plus metapipeline
     overlap (``core.scheduling`` -> ``core.cost.metapipeline_time``);
  3. prunes candidates whose ``core.memory.plan_memory`` footprint
     exceeds the VMEM budget (the paper's BRAM-capacity compile check);
  4. returns the argmin as a ``TilePlan``, memoized in a persistent
     on-disk tuning cache keyed by (pattern signature, input tensor
     shapes, dtype, budget, device kind, calibration-profile hash).

The objective is lexicographic: fewest main-memory words first (the
quantity Fig. 5c/7 optimize), then modeled metapipelined seconds, then
*largest* on-chip footprint (prefer reuse when traffic ties).

Hybrid analytic->measured mode (``measure="top_k"``): the analytic
enumeration + VMEM pruning above *shortlists* candidates, the top-k
are actually lowered (``codegen_pallas.lower_for_timing``) and timed
on device (``core.measure``: warmup excluded, median-of-k,
device-keyed persistent timing DB), the measured argmin wins, and the
samples update the per-device cost-model calibration profile
(``core.calibrate``) that subsequent analytic pricing consumes.  Both
the winning plan and every measurement are cached, so a second
exploration does zero lowering and zero execution.  Setting
``REPRO_MEASURE=top_k`` turns the hybrid mode on for every
``auto_tile=True`` kernel and fused pipeline without code changes.

The bottom half of the module is a library of *proxy programs*: small
PPL models of each Pallas kernel's loop structure (flash attention, the
SSD chunked scan, filter+reduce, GroupByFold).  The kernels' ``auto_tile``
paths build these proxies and ask ``explore`` for block sizes, so every
kernel shares one exploration engine and one tuning cache.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import os
from typing import Dict, List, Optional, Tuple, Union


from . import buckets, calibrate, ir, resilience, telemetry
from . import pipeline as plmod
from . import measure as measure_mod
from .cost import HBM_BYTES_PER_S, VMEM_BYTES, stream_seconds, traffic
from .memory import plan_memory
# The exploration-option constants and the unified Options surface live
# in core.options (a leaf module); re-exported here because this module
# is their historical home and every consumer imports them from dse.
from .options import (DEPTHS, MAX_POINTS, MEASURE_REPEAT, MEASURE_WARMUP,
                      MXU, SUBLANE, TOP_K, UNSET, Options)
from .scheduling import build_schedule, model_speedup
from .strip_mine import insert_tile_copies, strip_mine, tile

# TPU min-tile row (sublane) multiples per dtype: the fp32 8-row tile
# becomes 16 rows for bf16/f16 and 32 for int8/fp8 (packed sublanes).
_DTYPE_SUBLANE = {
    "bfloat16": 16, "float16": 16, "half": 16,
    "int8": 32, "uint8": 32,
    "float8_e4m3fn": 32, "float8_e5m2": 32, "float8_e4m3b11fnuz": 32,
}


def dtype_sublane(dtype) -> int:
    """Sublane (row) alignment for a dtype's minimum TPU tile."""
    return _DTYPE_SUBLANE.get(str(dtype), SUBLANE)

# Cost/memory-model revision, folded into every tuning-cache key: plans
# priced under older model semantics (e.g. the pre-PR-2 single-buffer
# accounting for strided loads, the PR-2 chain-only pipeline pricing
# superseded by the DAG accounting, the pre-calibration pricing that
# ignored device identity and launch overhead, the v4 fixed-depth-2
# pricing that predates the searched metapipeline buffer depth, or the
# v5 VMEM accounting in unpadded words) must not be replayed as cache
# hits.  CI keys its persistent REPRO_DSE_CACHE on this string too.
MODEL_VERSION = 6


# legacy kwargs whose ``None`` default means "unset" (merged below
# Options / env); ``False`` stays explicit (measure/cache/profile off)
def _resolve_options(options: Optional[Options], **kw) -> Options:
    """Merge one exploration's option layers: explicit kwarg >
    ``options=Options(...)`` > ``Options.from_env()`` > defaults.
    Returns a fully resolved ``Options`` (no ``UNSET`` fields)."""
    explicit = Options(**{k: v for k, v in kw.items()
                          if v is not None and v is not UNSET})
    return Options.merged(explicit, options or Options(),
                          Options.from_env()).resolved()


def _resolve_profile(profile):
    """``None`` -> the device's persisted calibration profile (if any),
    ``False`` -> uncalibrated, else the given profile."""
    if profile is False:
        return None
    if profile is None:
        return calibrate.load_profile()
    return profile


# --------------------------------------------------------------------------
# Tile plans
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """DSE result: per-pattern tile sizes plus the model's accounting.

    ``depths`` maps each tiled pattern name to the metapipeline buffer
    depth the search selected for its stage-crossing buffers (one
    searched depth per plan, recorded per pattern so downstream
    consumers key it like ``sizes``); ``depth`` is the scalar view.
    """

    sizes: Dict[str, Tuple[int, ...]]
    traffic_words: int
    vmem_bytes: int
    modeled_seconds: float
    explored: int = 0        # candidates priced
    pruned: int = 0          # candidates rejected by the VMEM budget
    thinned: bool = False    # search space was capped (MAX_POINTS)
    cached: bool = False     # served from the tuning cache
    measured: bool = False   # winner backed by a real on-device timing
    measured_seconds: float = 0.0   # winner's median wall time
    timed: int = 0           # candidates actually lowered and timed
    depths: Dict[str, int] = dataclasses.field(default_factory=dict)
    warm_start: bool = False  # adapted from a tuned bucket (core.buckets)
    bucket: str = ""          # donor bucket signature (warm starts only)
    key: str = ""             # tuning-cache key (dse.explain provenance)

    @property
    def depth(self) -> int:
        """The plan's stage-buffer depth (2 when unrecorded)."""
        return next(iter(self.depths.values()), 2)

    def to_json(self) -> Dict:
        return {
            "sizes": {k: list(v) for k, v in self.sizes.items()},
            "depths": {k: int(v) for k, v in self.depths.items()},
            "traffic_words": int(self.traffic_words),
            "vmem_bytes": int(self.vmem_bytes),
            "modeled_seconds": float(self.modeled_seconds),
            "explored": int(self.explored),
            "pruned": int(self.pruned),
            "thinned": bool(self.thinned),
            "measured": bool(self.measured),
            "measured_seconds": float(self.measured_seconds),
            "timed": int(self.timed),
            "key": str(self.key),
        }

    @classmethod
    def from_json(cls, d: Dict) -> "TilePlan":
        return cls(sizes={k: tuple(v) for k, v in d["sizes"].items()},
                   depths={k: int(v)
                           for k, v in d.get("depths", {}).items()},
                   traffic_words=int(d["traffic_words"]),
                   vmem_bytes=int(d["vmem_bytes"]),
                   modeled_seconds=float(d["modeled_seconds"]),
                   explored=int(d.get("explored", 0)),
                   pruned=int(d.get("pruned", 0)),
                   thinned=bool(d.get("thinned", False)),
                   measured=bool(d.get("measured", False)),
                   measured_seconds=float(d.get("measured_seconds", 0.0)),
                   timed=int(d.get("timed", 0)),
                   key=str(d.get("key", "")),
                   cached=True)


# --------------------------------------------------------------------------
# Persistent tuning cache
# --------------------------------------------------------------------------


def default_cache_path() -> str:
    return measure_mod.cache_sibling_path("dse_cache.json",
                                          "REPRO_DSE_CACHE")


# reserved top-level keys in the cache document: the candidate
# quarantine and the shape-bucket donor index (core.buckets); plan keys
# are 32-hex digests, so no collision is possible
QUARANTINE_KEY = "__quarantine__"
BUCKETS_KEY = "__buckets__"


class TuningCache:
    """On-disk key -> TilePlan store, crash-safe.

    Persistence goes through ``core.resilience``'s store layer:
    checksummed JSON, atomic replace, lock-protected read-modify-write
    on every put (concurrent explorations merge instead of clobbering),
    and a truncated or corrupt file is quarantined to
    ``<path>.corrupt`` (a warning names it) with the cache rebuilding
    fresh -- the cache is an accelerator, never a correctness
    dependency.

    The same document persists the **candidate quarantine**: a
    candidate whose lowering, timing or certification failed is
    recorded under ``__quarantine__`` (keyed per device + interpret
    mode) and is never re-attempted by later explorations.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: Optional[Dict[str, Dict]] = None

    def _load(self) -> Dict[str, Dict]:
        if self._data is None:
            self._data = resilience.load_store(self.path,
                                               label="DSE tuning cache")
        return self._data

    def _update(self, mutate) -> None:
        """Apply ``mutate(data)`` to the in-memory view AND, under the
        file lock, to the freshly re-read on-disk state -- entries a
        concurrent process wrote between our load and this put
        survive, and our view keeps its own entries even when the
        write fails (read-only FS)."""
        mine = self._load()
        mutate(mine)
        disk = resilience.locked_update(self.path, mutate,
                                        label="DSE tuning cache",
                                        prefix=".dse_cache.")
        q = {**mine.get(QUARANTINE_KEY, {}),
             **disk.get(QUARANTINE_KEY, {})}
        merged = {**mine, **disk}
        if q:
            merged[QUARANTINE_KEY] = q
        # bucket index: two-level nested merge (family -> bucket sig ->
        # donor entry), disk winning per bucket like plans do
        bk = dict(mine.get(BUCKETS_KEY, {}))
        for fam, ent in disk.get(BUCKETS_KEY, {}).items():
            bk[fam] = {**bk.get(fam, {}), **ent}
        if bk:
            merged[BUCKETS_KEY] = bk
        self._data = merged

    def get(self, key: str, cls=None) -> Optional["TilePlan"]:
        """Fetch a plan; ``cls`` selects the plan dataclass (default
        ``TilePlan``; ``PipelinePlan`` for joint pipeline plans)."""
        d = self._load().get(key)
        if d is None:
            return None
        try:
            return (cls or TilePlan).from_json(d)
        except (KeyError, TypeError, ValueError):
            return None

    def put(self, key: str, plan) -> None:
        doc = plan.to_json()
        self._update(lambda data: data.__setitem__(key, doc))

    def quarantine(self, key: str, kind: str, detail: str = "") -> None:
        """Persist a failed candidate so it is never re-attempted."""
        entry = {"kind": kind, "detail": detail[:500]}

        def mutate(data: Dict) -> None:
            data.setdefault(QUARANTINE_KEY, {})[key] = entry

        self._update(mutate)

    def quarantined(self, key: str) -> Optional[Dict]:
        """The quarantine record for ``key`` ({"kind", "detail"}), or
        None when the candidate has never failed."""
        q = self._load().get(QUARANTINE_KEY)
        entry = q.get(key) if isinstance(q, dict) else None
        return entry if isinstance(entry, dict) else None

    def bucket_entries(self, family: str) -> Dict[str, Dict]:
        """The shape-bucket donor index for one pattern family:
        {bucket signature: {"kind", "domains", "plan"}}
        (``core.buckets`` owns the format)."""
        bk = self._load().get(BUCKETS_KEY)
        fam = bk.get(family) if isinstance(bk, dict) else None
        return fam if isinstance(fam, dict) else {}

    def bucket_put(self, family: str, sig: str, entry: Dict) -> None:
        """Register a tuned plan as its bucket's warm-start donor."""
        def mutate(data: Dict) -> None:
            data.setdefault(BUCKETS_KEY, {}).setdefault(
                family, {})[sig] = entry

        self._update(mutate)

    def clear(self) -> None:
        self._data = {}
        try:
            os.unlink(self.path)
        except OSError:
            pass


def _resolve_cache(cache: Union[None, bool, str, "TuningCache"]
                   ) -> Optional[TuningCache]:
    """``None`` -> default on-disk cache, path/TuningCache -> that cache,
    ``False`` -> no caching."""
    if cache is False:
        return None
    if cache is None:
        return TuningCache()
    if isinstance(cache, str):
        return TuningCache(cache)
    return cache


def _reads_sig(p: ir.Pattern, enc: int = 0) -> Tuple:
    """Access descriptors in pre-order: (src, window, affine, index map).

    ``ir.signature`` covers domains/nesting/loads but not reads, and an
    untiled program carries all its shape information in reads -- two
    programs differing only in an access window must not share a key.
    Index maps are probed best-effort (non-affine maps hash as opaque).
    """
    from .affine import AffineMap

    out: List = []
    stack = enc + len(p.domain)
    for a in p.accesses:
        src = a.src.name if isinstance(a.src, ir.Tensor) \
            else type(a.src).__name__
        if isinstance(a.index_map, AffineMap):
            m: object = (a.index_map.base, a.index_map.mat)
        else:
            try:
                amap = AffineMap.probe(a.index_map, stack)
                m = (amap.base, amap.mat)
            except (TypeError, ValueError, IndexError):
                # unit probing a non-affine / non-integer map fails in
                # exactly these ways; anything else is a real bug in
                # the map and must surface, not hash as opaque
                m = "nonaffine"
        out.append((src, tuple(a.window), a.affine, m))
        if isinstance(a.src, ir.Pattern):
            out.append(_reads_sig(a.src, stack))
    if p.inner is not None:
        out.append(_reads_sig(p.inner, stack))
    return tuple(out)


def _key_context(device: Optional[str],
                 profile_hash: Optional[str]) -> Tuple[str, str]:
    """(device kind, calibration-profile hash) folded into every cache
    key: a plan tuned on one device, or priced under one calibration,
    must not be replayed on another device / after recalibration.
    Explicit values (including ``""`` to opt out, e.g. for timing-DB
    keys that identify the *computation*, not its pricing) pass through.
    """
    if device is None:
        device = measure_mod.device_kind()
    if profile_hash is None:
        profile_hash = calibrate.active_profile_hash(device)
    return device, profile_hash


def pattern_key(p: ir.Pattern, *,
                vmem_budget: int = VMEM_BYTES,
                align: int = MXU,
                extra: Tuple = (),
                device: Optional[str] = None,
                profile_hash: Optional[str] = None) -> str:
    """Tuning-cache key: structural signature + access descriptors +
    input shapes/dtypes + exploration constraints + device kind +
    calibration-profile hash.

    Any change to the pattern tree (domains, nesting, reads, tensor
    shapes or dtypes), to the constraints, to the device, or to the
    active calibration changes the key, so cached plans invalidate
    automatically instead of going stale.
    """
    device, profile_hash = _key_context(device, profile_hash)
    inputs = tuple((t.name, tuple(t.shape), t.dtype)
                   for t in ir.inputs_of(p))
    raw = repr((MODEL_VERSION, device, profile_hash,
                ir.signature(p), _reads_sig(p), inputs,
                int(vmem_budget), int(align), tuple(extra)))
    return hashlib.sha256(raw.encode()).hexdigest()[:32]


# --------------------------------------------------------------------------
# Candidate enumeration
# --------------------------------------------------------------------------


def axis_candidates(extent: int, align: int = MXU, *,
                    sublane: int = 1) -> List[int]:
    """Divisors of ``extent`` that are multiples of both
    ``min(align, extent)`` and the dtype ``sublane``, falling back to
    the full extent.

    Divisor (not power-of-two) enumeration admits ragged tiles -- a
    96-wide domain offers 24/48 in addition to the 8/16/32 ladder --
    while the multiple-of-align floor keeps every candidate expressible
    on the hardware (a non-128-multiple lane tile is not).  ``sublane``
    is the dtype row multiple (8 fp32 / 16 bf16 / 32 int8,
    ``dtype_sublane``).  The whole extent is always a candidate: there
    is nothing left to misalign against.
    """
    floor = min(align, extent)
    divs: List[int] = []
    d = 1
    while d * d <= extent:
        if extent % d == 0:
            divs.append(d)
            if d != extent // d:
                divs.append(extent // d)
        d += 1
    out = sorted(c for c in divs
                 if c == extent
                 or (c % floor == 0 and c % sublane == 0))
    return out or [extent]


def tile_space(p: ir.Pattern, *, align: int = MXU
               ) -> Dict[str, List[Tuple[int, ...]]]:
    """Per-named-pattern candidate tile tuples for every (untiled) domain.

    The full design space is the cross product over patterns; patterns
    that already carry a strided domain are left alone.  Candidate rows
    are aligned to the pattern dtype's sublane multiple
    (``dtype_sublane``), not the fp32-only 8-row assumption.
    """
    space: Dict[str, List[Tuple[int, ...]]] = {}
    for q in ir.walk(p):
        if q.strided or not q.domain or q.name in space:
            continue
        sub = dtype_sublane(q.dtype)
        per_dim = [axis_candidates(d, align, sublane=sub)
                   for d in q.domain]
        space[q.name] = [tuple(c) for c in itertools.product(*per_dim)]
    return space


def _thin(space: Dict[str, List[Tuple[int, ...]]],
          max_points: int) -> Tuple[Dict[str, List[Tuple[int, ...]]], bool]:
    """Halve the densest axis list (keeping endpoints) until the cross
    product is within budget.  Returns (space, was_thinned)."""
    def total(s):
        t = 1
        for v in s.values():
            t *= len(v)
        return t

    thinned = False
    space = {k: list(v) for k, v in space.items()}
    while total(space) > max_points:
        name = max(space, key=lambda k: len(space[k]))
        v = space[name]
        if len(v) <= 2:
            break
        space[name] = v[::2] if v[-1] == v[::2][-1] else v[::2] + [v[-1]]
        thinned = True
    return space, thinned


# --------------------------------------------------------------------------
# Pricing
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Priced:
    sizes: Dict[str, Tuple[int, ...]]
    traffic_words: int
    vmem_bytes: int
    modeled_seconds: float           # uncalibrated analytic prediction
    calibrated_seconds: float = -1.0  # profile-adjusted (== analytic
    steps: int = 1                    # when uncalibrated); grid steps
    depth: int = 2                    # metapipeline buffer depth

    def __post_init__(self):
        if self.calibrated_seconds < 0:
            object.__setattr__(self, "calibrated_seconds",
                               self.modeled_seconds)


def grid_steps(p: ir.Pattern, sizes: Dict[str, Tuple[int, ...]]) -> int:
    """Kernel grid steps the tiled program executes: the product of
    (extent / tile) over every tiled domain.  The trip count the
    calibration model charges per-pattern launch overhead against."""
    steps = 1
    for q in ir.walk(p):
        if q.name not in sizes or not q.domain:
            continue
        for d, s in zip(q.domain, sizes[q.name]):
            steps *= max(1, -(-d // max(int(s), 1)))
    return steps


def _tile_ir(p: ir.Pattern, sizes: Dict[str, Tuple[int, ...]],
             vmem_budget_words: int) -> ir.Pattern:
    try:
        return tile(p, sizes, vmem_budget_words=vmem_budget_words)
    except resilience.EXPECTED_ERRORS as e:
        # interchange/lift may not apply to every proxy shape; the
        # strip-mine + copy-insertion core always does.  Recorded once
        # per pattern (price() calls this per candidate) so the
        # degradation is observable without spamming the event log;
        # real bugs (AttributeError etc.) propagate.
        resilience.record_once(
            "tile", resilience.classify(e),
            f"{type(p).__name__}:{p.name}", "fallback",
            f"tile() failed ({e}); strip-mine+copies fallback")
        return insert_tile_copies(strip_mine(p, sizes),
                                  vmem_budget_words=vmem_budget_words)


def price(p: ir.Pattern, sizes: Dict[str, Tuple[int, ...]], *,
          vmem_budget: int = VMEM_BYTES,
          bytes_per_word: int = 4,
          profile=False,
          depth: int = 2) -> Optional[Priced]:
    """Tile ``p`` with ``sizes`` and price it; None if it busts VMEM.

    Modeled seconds = HBM stream time of the tiled IR's main-memory
    reads, scaled by the metapipeline time ratio of its schedule
    (``metapipeline_time`` steady state vs. sequential).  ``depth`` is
    the stage-buffer depth the schedule and VMEM plan are built with:
    the plan charges ``depth x`` bytes per stage-crossing buffer (so a
    deep candidate can bust VMEM where the shallow one fits) and the
    time model charges whatever DMA issue latency ``depth - 1``
    iterations of lookahead cannot hide.  With a calibration profile
    (``profile``: None -> the device's persisted one, False ->
    uncalibrated), ``calibrated_seconds`` reprices the same overlapped
    stream at the *measured* effective bandwidth plus the per-pattern
    launch overhead per grid step.
    """
    prof = _resolve_profile(profile)
    t = _tile_ir(p, sizes, vmem_budget // bytes_per_word)
    plan = plan_memory(t, vmem_budget_bytes=vmem_budget, depth=depth)
    if not plan.fits:
        return None
    # an affine tensor read left in place means its tile copy would not
    # fit on-chip (insert_tile_copies' streaming fallback): over-VMEM
    for q in ir.walk(t):
        for a in q.accesses:
            if isinstance(a.src, ir.Tensor) and a.affine:
                return None
    tr = traffic(t)
    seconds = stream_seconds(tr.total_reads, bytes_per_word=bytes_per_word)
    mp = build_schedule(t, vmem_budget // bytes_per_word, depth=depth)
    if mp is not None:
        body_words = sum(s.words for s in mp.stages if s.kind == "body")
        seq, pipe, _ = model_speedup(mp, flops_per_body=body_words * 100.0)
        if seq > 0 and pipe > 0:
            # pipe/seq < 1 is the overlap speedup; > 1 means exposed
            # DMA latency dominates the shallow pipeline -- both priced
            seconds *= pipe / seq
    steps = grid_steps(p, sizes)
    calibrated = calibrate.predicted_seconds(
        type(p).__name__, seconds * HBM_BYTES_PER_S, steps, profile=prof)
    return Priced(dict(sizes), tr.total_reads, plan.total_bytes, seconds,
                  calibrated, steps, depth=depth)


def _rank_key(a: Priced) -> Tuple:
    """Lexicographic: traffic, then (calibrated) modeled time, then
    shallowest depth, then prefer reuse."""
    # depth breaks seconds ties BEFORE the -vmem reuse term: once the
    # exposed-latency term saturates, deeper variants tie on seconds
    # and their larger footprint must not win via the reuse preference
    return (a.traffic_words, a.calibrated_seconds, a.depth, -a.vmem_bytes)


# --------------------------------------------------------------------------
# Exploration
# --------------------------------------------------------------------------


def shortlist(p: ir.Pattern, *,
              vmem_budget: int = VMEM_BYTES,
              align: int = MXU,
              space: Optional[Dict[str, List[Tuple[int, ...]]]] = None,
              max_points: int = MAX_POINTS,
              profile=False,
              depths: Tuple[int, ...] = DEPTHS
              ) -> Tuple[List[Priced], bool, int, int]:
    """Analytic enumeration + VMEM pruning, every feasible candidate
    priced and sorted best-first by the lexicographic objective.

    The candidate space is the cross product of tile sizes x ``depths``
    (metapipeline buffer depths): a deep variant of a tile that would
    bust VMEM is pruned exactly like an oversized tile.  Returns
    ``(candidates, thinned, explored, pruned)``; the plain analytic
    argmin is ``candidates[0]``, the hybrid mode lowers and times
    ``candidates[:top_k]``.
    """
    prof = _resolve_profile(profile)
    if space is None:
        space = tile_space(p, align=align)
    space, thinned = _thin(space, max_points)
    names = sorted(space)

    cands: List[Priced] = []
    explored = pruned = 0
    for combo in itertools.product(*(space[n] for n in names)):
        sizes = dict(zip(names, combo))
        for d in depths:
            priced = price(p, sizes, vmem_budget=vmem_budget,
                           profile=prof if prof is not None else False,
                           depth=d)
            explored += 1
            if priced is None:
                pruned += 1
                continue
            cands.append(priced)
    cands.sort(key=_rank_key)
    return cands, thinned, explored, pruned


@dataclasses.dataclass(frozen=True)
class CandidateTiming:
    """One shortlisted candidate, actually lowered and timed.  ``plan``
    is the candidate as the plan it would ship as: a ``TilePlan``, or a
    fully fused one-group ``PipelinePlan``."""

    plan: Union[TilePlan, "PipelinePlan"]
    analytic_seconds: float      # uncalibrated model prediction
    steps: int
    measurement: measure_mod.Measurement
    lowering: str                # "pallas" | "oracle" | "cached"

    @property
    def calibrated_seconds(self) -> float:
        """The profile-adjusted model prediction."""
        return self.plan.modeled_seconds


def _timing_shortlist(space, ranked: List, k: int) -> List[Tuple]:
    """Best-first prefix of the analytic search's ``ranked`` candidates
    with at most ``k`` entries and one per timing identity
    (``space.ident``), as ``(plan, analytic seconds, grid steps)``."""
    out: List[Tuple] = []
    seen = set()
    for c in ranked:
        point = space.point(c)
        ident = space.ident(point[0])
        if ident in seen:
            continue
        seen.add(ident)
        out.append(point)
        if len(out) >= k:
            break
    return out


def _time_candidates(space, points: List[Tuple], *,
                     timing_db, warmup: int, repeat: int,
                     policy: Optional[resilience.Policy] = None,
                     cache: Optional[TuningCache] = None,
                     observe: bool = True) -> List[CandidateTiming]:
    """Lower + time shortlisted candidates (timing-DB memoized), and
    with ``observe`` fold the samples into the calibration profile.

    Each lower+time runs under the resilience policy's deadline with
    transient retry; an expected failure (no template, numeric blowup,
    injected fault, deadline miss) classifies the candidate, records a
    structured event, and -- when ``cache`` is given -- quarantines it
    so no later exploration re-attempts the same crash.  Unexpected
    exceptions still propagate: a real bug must surface.
    """
    pol = resilience.resolve_policy(policy)
    out: List[CandidateTiming] = []
    for plan, analytic_s, steps in points:
        # identifies the computation, not its pricing: no device /
        # profile-hash component (TimingDB adds the device itself)
        key = space.key(("timing",) + space.ident(plan), device="",
                        profile_hash="")
        qkey = "time|" + measure_mod.TimingDB.full_key(key)
        if cache is not None:
            q = cache.quarantined(qkey)
            if q is not None:
                resilience.record_once(
                    "time", q.get("kind", "unknown"), qkey, "skipped",
                    "previously quarantined candidate not re-attempted")
                continue
        how = ["cached"]

        def make_fn(plan=plan, how=how):
            fn, how[0] = space.lower(plan)
            return fn

        try:
            m = resilience.call_guarded(
                lambda: measure_mod.timed(key, make_fn, db=timing_db,
                                          warmup=warmup, repeat=repeat),
                stage="time", key=qkey, policy=pol)
        except resilience.CandidateFailure as e:
            resilience.record("time", e.kind, qkey, "quarantined",
                              e.detail)
            if cache is not None:
                cache.quarantine(qkey, e.kind, e.detail)
            continue
        out.append(CandidateTiming(plan, analytic_s, steps, m, how[0]))
    if observe:
        _observe(space, out)
    return out


def _observe(space, timings: List[CandidateTiming]) -> None:
    """Fold one measured shortlist into the device calibration profile,
    and set the model-accuracy gauges of its pattern family from the
    (calibrated prediction, measured median) pairs:
    ``model.drift.<kind>`` the mean relative |predicted - measured| /
    measured, ``model.spearman.<kind>`` the rank correlation of the
    analytic ordering against the measured one.  Always-on (gauges are
    cheap scalars): ``benchmarks/check_regression.py`` prints them next
    to the gate output without needing ``REPRO_TRACE``."""
    if not timings:
        return
    calibrate.observe([calibrate.Sample(
        workload=space.workload, kind=space.calib_kind,
        stream_bytes=t.analytic_seconds * HBM_BYTES_PER_S,
        steps=t.steps, measured_s=t.measurement.median_s,
        key=f"{space.workload}|{space.sample_id(t.plan)}")
        for t in timings])
    pairs = [(t.calibrated_seconds, t.measurement.median_s)
             for t in timings]
    drift = sum(abs(p - m) / max(m, 1e-12) for p, m in pairs) / len(pairs)
    telemetry.gauge(f"model.drift.{space.calib_kind}", drift)
    if len(pairs) >= 2:
        telemetry.gauge(f"model.spearman.{space.calib_kind}",
                        measure_mod.spearman([p for p, _ in pairs],
                                             [m for _, m in pairs]))


def _record_plan(plan, *, source: str, **extra) -> None:
    """Stash a plan's exploration provenance for ``explain`` (tracing
    only; the record store is a bounded LRU in ``core.telemetry``).
    Merges into any existing record under the same key: a cache hit
    updates ``source`` without losing the original exploration's rank
    tables, and a warm start's ``retune_tag`` survives the background
    re-tune recording its own exploration under the promoted key."""
    if not telemetry.enabled() or not plan.key:
        return
    prev = telemetry.get_record("plan", plan.key)
    payload = dict(prev) if isinstance(prev, dict) else {}
    payload.update({"source": source, **extra})
    telemetry.put_record("plan", plan.key, payload)


def measured_shortlist(p: ir.Pattern, *,
                       top_k: int = TOP_K,
                       vmem_budget: int = VMEM_BYTES,
                       align: int = MXU,
                       space: Optional[Dict[str, List[Tuple[int, ...]]]]
                       = None,
                       max_points: int = MAX_POINTS,
                       profile=None,
                       timing_db=None,
                       warmup: int = MEASURE_WARMUP,
                       repeat: int = MEASURE_REPEAT,
                       calibrate_update: bool = True,
                       policy: Optional[resilience.Policy] = None,
                       cache: Union[None, bool, str, TuningCache] = False
                       ) -> List[CandidateTiming]:
    """Hybrid step as a library call: analytic shortlist, lower + time
    the top-k, optionally fold the samples into the device calibration
    profile.  ``benchmarks/run.py --measure`` builds its analytic-vs-
    measured rank-correlation table from exactly these records.

    ``policy`` bounds each lower+time with a deadline and transient
    retry; ``cache`` (default off for the library call) enables the
    persistent candidate quarantine shared with ``explore``.
    """
    s = _TileSpace(p, space, Options(vmem_budget=vmem_budget, align=align,
                                     max_points=max_points).resolved())
    cands, _, _, _ = shortlist(p, vmem_budget=vmem_budget, align=align,
                               space=s.space, max_points=max_points,
                               profile=profile)
    return _time_candidates(s, _timing_shortlist(s, cands, max(top_k, 1)),
                            timing_db=timing_db, warmup=warmup,
                            repeat=repeat, policy=policy,
                            cache=_resolve_cache(cache),
                            observe=calibrate_update)


def explore(p: ir.Pattern, *,
            vmem_budget: Optional[int] = None,
            align: Optional[int] = None,
            space: Optional[Dict[str, List[Tuple[int, ...]]]] = None,
            cache: Union[None, bool, str, TuningCache] = None,
            max_points: Optional[int] = None,
            measure: Optional[str] = None,
            top_k: Optional[int] = None,
            timing_db=None,
            profile=None,
            warmup: Optional[int] = None,
            repeat: Optional[int] = None,
            depths: Optional[Tuple[int, ...]] = None,
            policy: Optional[resilience.Policy] = None,
            bucketing: Optional[bool] = None,
            options: Optional[Options] = None) -> TilePlan:
    """Design-space exploration over tile sizes AND metapipeline buffer
    depths for any pattern program.

    ``p`` is the *untiled* program.  ``cache`` selects the tuning cache:
    ``None`` -> the default on-disk cache, a path or ``TuningCache`` ->
    that cache, ``False`` -> no caching.  ``depths`` is the set of
    stage-buffer depths enumerated per tile candidate (default
    ``DEPTHS = (2, 3, 4)``): each (sizes, depth) pair is priced with
    ``depth x`` VMEM charged per stage-crossing buffer and the exposed
    DMA latency the depth cannot hide; ties in modeled seconds break
    toward the shallowest depth.  The winner's depth is recorded on
    ``TilePlan.depths``.  Raises ``ValueError`` when no candidate fits
    the VMEM budget.

    ``measure="top_k"`` (or ``REPRO_MEASURE=top_k``) switches to hybrid
    analytic->measured mode: the analytic shortlist's top ``top_k``
    candidates (distinct tile assignments; depth variants of one tile
    share a measurement because the single-pattern templates delegate
    buffering to the Pallas pipeliner) are lowered
    (``codegen_pallas.lower_for_timing``) and timed
    (median-of-``repeat``, ``warmup`` excluded, memoized in the
    device-keyed ``timing_db``), the measured argmin wins, and the
    samples recalibrate the device profile before the plan is cached --
    so a second call is a pure cache hit: zero lowering, zero execution.

    The measured path is fault-tolerant (``core.resilience``): each
    lower+time runs under ``policy``'s deadline with transient retry,
    failing candidates are quarantined in the tuning cache (never
    re-attempted), and the measured winner is *certified* against the
    ``codegen_jax`` oracle before promotion -- a winner that times well
    but computes wrong numbers is quarantined and the next-fastest
    certified candidate wins instead.  When every measured candidate
    fails, the analytic argmin ships (recorded as a fallback event);
    ``explore`` never raises for a candidate-level failure.

    Every kwarg can instead arrive packed in ``options=Options(...)``;
    explicit kwargs win over the options object, which wins over the
    ``REPRO_*`` env vars (``Options.from_env``), which win over the
    defaults.  ``bucketing=True`` adds the shape-bucketed mode
    (``core.buckets``): a cold shape whose pattern family has tuned
    buckets returns a warm-start plan immediately (nearest bucket's
    tiles re-fitted, zero lowering) while a background re-tune --
    deadline-bounded by ``policy`` -- explores the exact shape and
    promotes its certified winner into the cache.
    """
    o = _resolve_options(options, vmem_budget=vmem_budget, align=align,
                         cache=cache, max_points=max_points,
                         measure=measure, top_k=top_k,
                         timing_db=timing_db, profile=profile,
                         warmup=warmup, repeat=repeat, depths=depths,
                         policy=policy, bucketing=bucketing)
    if o.trace:
        telemetry.enable()
    with telemetry.span("dse.explore", kind=type(p).__name__,
                        pattern=p.name) as sp:
        return _explore(_TileSpace(p, space, o), o, sp)


# --------------------------------------------------------------------------
# The exploration engine: one skeleton over two search spaces
# --------------------------------------------------------------------------


def _explore(space, o: Options, sp):
    """One exploration over ``space`` (a ``_TileSpace`` or a
    ``_PipelineSpace``, which hold everything the two engines do
    differently): tuning-cache lookup; bucketed warm start with a
    background re-tune; the analytic search; in measured mode the top-k
    timed and the fastest *certified* one promoted; then the plan is
    cached under its post-calibration key, registered as its bucket's
    donor and its provenance recorded.  ``sp`` is the caller's span."""
    tc = _resolve_cache(o.cache)
    bucketing_on = o.bucketing and tc is not None and space.bucketable
    key = space.key()
    if tc is not None:
        hit = tc.get(key, space.plan_cls)
        if hit is not None:
            if bucketing_on:
                buckets.note("exact_hits")
            telemetry.count("dse.cache_hits")
            hit = dataclasses.replace(hit, key=key)
            sp.set(source="cache")
            _record_plan(hit, source="cache")
            return hit

    if bucketing_on:
        warm = buckets.warm_start(space, tc)
        if warm is not None:
            buckets.note("warm_hits")
            pol = resilience.resolve_policy(o.policy)
            # cache=False: the re-tune must not write the cache itself
            # -- only its *certified* winner is promoted, below
            retune_opts = dataclasses.replace(o, bucketing=False,
                                              cache=False)
            tag = f"{space.tag}|{key}"

            def _promote(plan) -> None:
                # key recomputed at promotion time: the background
                # explore may have refreshed the calibration profile
                tc.put(space.key(), plan)
                buckets.record(space, plan, tc)

            buckets.schedule_retune(
                tag, lambda: space.retune(retune_opts),
                certify=lambda plan: resilience.certify_guarded(
                    lambda: space.certify(plan), key="retune|" + tag,
                    policy=pol),
                promote=_promote, policy=pol)
            warm = dataclasses.replace(warm, key=key)
            sp.set(source="warm_start", bucket=warm.bucket)
            _record_plan(warm, source="warm_start", bucket=warm.bucket,
                         retune_tag=tag)
            return warm
        buckets.note("misses")

    plan, ranked = space.search()
    prov_measured: List[Dict] = []
    prov_cert: List[Dict] = []
    n_short = n_timed = 0
    if o.measure == "top_k" and space.one_kernel(plan):
        pol = resilience.resolve_policy(o.policy)
        with telemetry.span("dse.measure", top_k=int(o.top_k)) as msp:
            top = _timing_shortlist(space, ranked, max(o.top_k, 1))
            n_short = len(top)
            timings = _time_candidates(space, top, timing_db=o.timing_db,
                                       warmup=o.warmup, repeat=o.repeat,
                                       policy=pol, cache=tc)
            n_timed = len(timings)
            msp.set(shortlisted=n_short, timed=n_timed)
            by_time = sorted(timings,
                             key=lambda t: (t.measurement.median_s,
                                            t.plan.traffic_words,
                                            t.plan.depth,
                                            -t.plan.vmem_bytes))
            prov_measured = [space.timing_row(t) for t in by_time]
            for win in by_time:
                if pol.certify:
                    ckey = "certify|" + measure_mod.TimingDB.full_key(
                        space.key(("certify",) + space.ident(win.plan),
                                  device="", profile_hash=""))
                    row = space.row(win.plan)
                    if tc is not None \
                            and tc.quarantined(ckey) is not None:
                        # failed certification in a past run
                        prov_cert.append({**row, "ok": False,
                                          "reason": "quarantined"})
                        continue
                    ok, reason = resilience.certify_guarded(
                        lambda w=win: space.certify(w.plan),
                        key=ckey, policy=pol)
                    prov_cert.append({**row, "ok": bool(ok),
                                      "reason": reason})
                    if not ok:
                        resilience.record("certify", "certify-failed",
                                          ckey, "quarantined", reason)
                        if tc is not None:
                            tc.quarantine(ckey, "certify-failed", reason)
                        continue
                plan = dataclasses.replace(
                    win.plan, measured=True,
                    measured_seconds=win.measurement.median_s,
                    timed=n_timed)
                break
            else:
                # every shortlisted candidate failed timing or
                # certification: the analytic plan ships, uncertified
                # measured data never does
                resilience.record(
                    "explore", "no-measured-winner", space.workload,
                    "fallback",
                    f"{n_timed} timed, 0 certified; analytic plan "
                    "promoted instead")

    # key recomputed AFTER the calibration update: the next call
    # prices under the new profile hash and must hit this entry
    plan = dataclasses.replace(plan, key=space.key())
    if tc is not None:
        tc.put(plan.key, plan)
        if bucketing_on:
            buckets.record(space, plan, tc)
    sp.set(source="explored", explored=plan.explored, pruned=plan.pruned,
           **space.span_attrs(plan), timed=plan.timed)
    _record_plan(
        plan, source="explored",
        enumerated=plan.explored,
        pruned={"vmem": plan.pruned,
                **space.pruned_reasons(len(ranked), n_short, n_timed,
                                       plan)},
        analytic_ranks=[
            {**space.row(c), "depth": int(c.depth),
             "traffic_words": int(c.traffic_words),
             "calibrated_seconds": float(c.modeled_seconds)}
            for c, _, _ in map(space.point, ranked[:max(int(o.top_k), 3)])],
        measured_ranks=prov_measured,
        certification=prov_cert)
    return plan


def _search_extra(o: Options) -> Tuple:
    """Key components both spaces add after their candidate set: a
    depth-restricted exploration must not share cache entries with the
    default-depths one, nor a measured exploration with a purely
    analytic one."""
    return ((("depths",) + tuple(int(d) for d in o.depths),)
            + ((("measure", o.measure, int(o.top_k)),) if o.measure
               else ()))


def _fit(cands: List[int], want: int) -> int:
    """The largest candidate <= ``want`` (else the smallest): a donor
    bucket's tile re-fitted onto a cold shape's own candidate grid."""
    le = [c for c in cands if c <= want]
    return max(le) if le else min(cands)


class _TileSpace:
    """The pattern engine's search space: a tile per named domain axis,
    crossed with the buffer depths and ranked by ``_rank_key``.  Every
    plan is one kernel."""

    kind = tag = "tile"      # bucket-index kind, re-tune tag prefix
    plan_cls = TilePlan

    def __init__(self, p: ir.Pattern, space, o: Options):
        self.p, self.o = p, o
        # explicit ``space=`` pins the candidate set to the caller's
        # shape: a donor bucket's plan would not be comparable, so
        # bucketing only engages for the default space
        self.bucketable = space is None
        self.space, self.thinned = _thin(
            tile_space(p, align=o.align) if space is None else space,
            o.max_points)
        # the key covers the *resolved* candidate space: a caller-
        # restricted or thinned exploration must not share cache
        # entries with a full one
        self.extra = tuple((n, tuple(self.space[n]))
                           for n in sorted(self.space)) + _search_extra(o)
        self.counts: Dict = {}   # the search's accounting, for its plans
        self.calib_kind = type(p).__name__
        shapes = "+".join(f"{t.name}:{'x'.join(map(str, t.shape))}"
                          for t in ir.inputs_of(p))
        self.workload = f"{self.calib_kind}:{p.name}:{shapes}"

    def key(self, extra: Optional[Tuple] = None, **ctx) -> str:
        return pattern_key(self.p, vmem_budget=self.o.vmem_budget,
                           align=self.o.align,
                           extra=self.extra if extra is None else extra,
                           **ctx)

    def search(self) -> Tuple[TilePlan, List[Priced]]:
        o = self.o
        # the space was thinned in __init__: keep that flag (re-thinning
        # an already-thinned space is a no-op and would report False)
        with telemetry.span("dse.shortlist", thinned=self.thinned) as ssp:
            cands, _, explored, pruned = shortlist(
                self.p, vmem_budget=o.vmem_budget, align=o.align,
                space=self.space, max_points=o.max_points,
                profile=o.profile, depths=o.depths)
            ssp.set(explored=explored, pruned=pruned, feasible=len(cands))
        if not cands:
            raise ValueError(
                f"DSE: no tile candidate fits VMEM budget {o.vmem_budget} "
                f"B ({explored} candidates over {sorted(self.space)})")
        self.counts = {"explored": explored, "pruned": pruned,
                       "thinned": self.thinned}
        return self.point(cands[0])[0], cands

    def point(self, c: Priced) -> Tuple[TilePlan, float, int]:
        """A ranked candidate as (plan, analytic seconds, grid steps)."""
        return TilePlan(sizes={k: tuple(v) for k, v in c.sizes.items()},
                        depths={k: int(c.depth) for k in c.sizes},
                        traffic_words=c.traffic_words,
                        vmem_bytes=c.vmem_bytes,
                        modeled_seconds=c.calibrated_seconds,
                        **self.counts), c.modeled_seconds, c.steps

    def one_kernel(self, plan: TilePlan) -> bool:
        return True

    def ident(self, plan: TilePlan) -> Tuple:
        """Timing and certification identity: the tile sizes, without
        the depth.  Depth variants of one tile execute identically under
        the single-pattern templates (the Mosaic pipeliner owns the
        BlockSpec buffering), so timing them separately would spend the
        whole top-k on copies of a single measurement."""
        return (tuple(sorted((k, tuple(v))
                             for k, v in plan.sizes.items())),)

    def row(self, plan: TilePlan) -> Dict:
        return {"sizes": {k: list(v) for k, v in plan.sizes.items()}}

    def timing_row(self, t: CandidateTiming) -> Dict:
        return {**self.row(t.plan), "depth": int(t.plan.depth),
                "median_s": float(t.measurement.median_s),
                "lowering": t.lowering}

    def sample_id(self, plan: TilePlan) -> str:
        return str(sorted(plan.sizes.items()))

    def lower(self, plan: TilePlan):
        from .codegen_pallas import lower_for_timing
        return lower_for_timing(self.p, plan.sizes,
                                vmem_budget=self.o.vmem_budget)

    def certify(self, plan: TilePlan) -> Tuple[bool, str]:
        return resilience.certify_tile_plan(self.p, plan.sizes,
                                            vmem_budget=self.o.vmem_budget)

    def retune(self, options: Options) -> TilePlan:
        return explore(self.p, options=options)

    def span_attrs(self, plan: TilePlan) -> Dict:
        return {}

    def pruned_reasons(self, n_ranked: int, n_short: int, n_timed: int,
                       plan: TilePlan) -> Dict:
        return {"dominated": max(n_ranked - n_short, 0) if n_short else 0,
                "measure_failures": max(n_short - n_timed, 0)}

    def family(self) -> str:
        return buckets.tile_family(self.p, vmem_budget=self.o.vmem_budget,
                                   align=self.o.align)

    def bucket_domains(self) -> Dict[str, Tuple[int, ...]]:
        return buckets.tile_buckets(self.p, align=self.o.align)

    def refit(self, donor: TilePlan) -> Optional[TilePlan]:
        """``donor``'s tiles re-fitted onto this shape's candidate grid
        at the donor's depth, priced uncalibrated (None: no fit)."""
        sizes: Dict[str, Tuple[int, ...]] = {}
        for q in ir.walk(self.p):
            if q.strided or not q.domain or q.name in sizes:
                continue
            dt = donor.sizes.get(q.name)
            if dt is None or len(dt) != len(q.domain):
                return None
            sub = dtype_sublane(q.dtype)
            sizes[q.name] = tuple(
                _fit(axis_candidates(extent, self.o.align, sublane=sub), t)
                for extent, t in zip(q.domain, dt))
        priced = price(self.p, sizes, vmem_budget=self.o.vmem_budget,
                       profile=False, depth=donor.depth)
        return None if priced is None else self.point(priced)[0]


# --------------------------------------------------------------------------
# Joint exploration for pipelines (fused multi-pattern programs)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """Joint DSE result for a pipeline DAG: streaming tiles plus the
    fusion grouping.

    ``groups`` are contiguous ``[start, end)`` ranges over the
    pipeline's *topological* stage order; a single group spanning the
    whole DAG means fully fused (intermediates are VMEM-resident,
    inter-stage HBM traffic = 0).  More than one group is the split
    fallback: every intermediate crossing a group boundary round-trips
    HBM, the cuts are the cheapest under the traffic model, and each
    group carries its own streaming tile in ``group_blocks`` (the split
    paths need not share a block size).  ``block`` is the first group's
    tile -- for a fused plan, the tile of the whole megakernel.

    ``depths`` (parallel to ``group_blocks``) records each group's
    searched metapipeline buffer depth: the stage scratch and input
    blocks of that group's megakernel rotate ``depth`` copies
    (``codegen_pallas.lower_fused_dag``), priced against the latency
    they hide (``cost.metapipeline_time``).  ``depth`` is the first
    group's value.
    """

    block: int
    groups: Tuple[Tuple[int, int], ...]
    traffic_words: int            # fused plan: HBM reads + writes
    unfused_traffic_words: int    # every intermediate round-trips HBM
    vmem_bytes: int               # max per-group footprint
    modeled_seconds: float
    group_blocks: Tuple[int, ...] = ()
    explored: int = 0
    pruned: int = 0
    cached: bool = False
    measured: bool = False          # winner backed by a real timing
    measured_seconds: float = 0.0   # winner's median wall time
    timed: int = 0                  # candidates lowered and timed
    depths: Tuple[int, ...] = ()    # per-group stage-buffer depth
    warm_start: bool = False        # adapted from a tuned bucket
    bucket: str = ""                # donor bucket signature
    key: str = ""                   # tuning-cache key (dse.explain)

    def __post_init__(self):
        if not self.group_blocks:
            object.__setattr__(self, "group_blocks",
                               (self.block,) * len(self.groups))
        if not self.depths:
            object.__setattr__(self, "depths", (2,) * len(self.groups))

    @property
    def depth(self) -> int:
        """The first group's stage-buffer depth (the whole megakernel's
        depth for a fused plan)."""
        return self.depths[0] if self.depths else 2

    @property
    def fused(self) -> bool:
        return len(self.groups) == 1

    @property
    def traffic_ratio(self) -> float:
        """Unfused / fused HBM words (>= 1: the fusion win)."""
        return self.unfused_traffic_words / max(self.traffic_words, 1)

    def to_json(self) -> Dict:
        return {
            "block": int(self.block),
            "groups": [list(g) for g in self.groups],
            "group_blocks": [int(b) for b in self.group_blocks],
            "depths": [int(d) for d in self.depths],
            "traffic_words": int(self.traffic_words),
            "unfused_traffic_words": int(self.unfused_traffic_words),
            "vmem_bytes": int(self.vmem_bytes),
            "modeled_seconds": float(self.modeled_seconds),
            "explored": int(self.explored),
            "pruned": int(self.pruned),
            "measured": bool(self.measured),
            "measured_seconds": float(self.measured_seconds),
            "timed": int(self.timed),
            "key": str(self.key),
        }

    @classmethod
    def from_json(cls, d: Dict) -> "PipelinePlan":
        return cls(block=int(d["block"]),
                   groups=tuple(tuple(g) for g in d["groups"]),
                   group_blocks=tuple(int(b)
                                      for b in d.get("group_blocks", ())),
                   depths=tuple(int(x) for x in d.get("depths", ())),
                   traffic_words=int(d["traffic_words"]),
                   unfused_traffic_words=int(d["unfused_traffic_words"]),
                   vmem_bytes=int(d["vmem_bytes"]),
                   modeled_seconds=float(d["modeled_seconds"]),
                   explored=int(d.get("explored", 0)),
                   pruned=int(d.get("pruned", 0)),
                   measured=bool(d.get("measured", False)),
                   measured_seconds=float(d.get("measured_seconds", 0.0)),
                   timed=int(d.get("timed", 0)),
                   key=str(d.get("key", "")),
                   cached=True)


def pipeline_key(pipe, *, vmem_budget: int = VMEM_BYTES,
                 align: int = MXU, extra: Tuple = (),
                 device: Optional[str] = None,
                 profile_hash: Optional[str] = None) -> str:
    """Tuning-cache key over the pipeline's *topological DAG*
    signature: every stage's structural signature, access descriptors,
    input tensor shapes/dtypes -- hashed in canonical topological order
    -- plus the wiring edges, the output set, the exploration
    constraints, the device kind and the calibration-profile hash.
    Any stage or wiring change invalidates the cached joint plan;
    reordering the declaration of independent stages does not (the DAG
    is the same program)."""
    device, profile_hash = _key_context(device, profile_hash)
    parts = []
    for s in plmod.topo_stages(pipe):
        inputs = tuple((t.name, tuple(t.shape), t.dtype)
                       for t in ir.inputs_of(s))
        # ir.signature omits a Map's elem_shape; the stage output shape
        # is part of the wiring, so hash it explicitly
        parts.append((s.name, ir.signature(s), _reads_sig(s), inputs,
                      s.dtype, tuple(s.shape)))
    edges = tuple(sorted(set(plmod._edges(pipe))))
    raw = repr((MODEL_VERSION, device, profile_hash, pipe.name,
                tuple(parts), edges,
                tuple(plmod.output_names(pipe)),
                int(vmem_budget), int(align), tuple(extra)))
    return hashlib.sha256(raw.encode()).hexdigest()[:32]


def _pipeline_candidates(pipe, align: int, max_points: int) -> List[int]:
    sub = max(dtype_sublane(s.dtype) for s in plmod.topo_stages(pipe))
    space, _ = _thin({"extent": axis_candidates(pipe.shared_extent, align,
                                                sublane=sub)}, max_points)
    return space["extent"]


def _price_pipeline_group(sub_pipe, b: int, *, vmem_budget: int,
                          profile, counters: Dict[str, int],
                          depth: int = 2):
    """Price the sub-pipeline fused at tile ``b`` with stage-buffer
    ``depth``: returns ``(hbm_words, vmem_bytes, analytic_s,
    calibrated_s, steps)`` or None when it busts VMEM / cannot fuse."""
    budget_words = max(vmem_budget // 4, 1)
    try:
        fdag = plmod.fuse_dag(sub_pipe, b, vmem_budget_words=budget_words)
    except (ValueError, NotImplementedError):
        return None
    counters["explored"] += 1
    mem = plan_memory(fdag.patterns, vmem_budget_bytes=vmem_budget,
                      depth=depth)
    if not mem.fits:
        counters["pruned"] += 1
        return None
    for t in fdag.patterns:   # streaming fallback left in place
        for q in ir.walk(t):
            for a in q.accesses:
                if isinstance(a.src, ir.Tensor) and a.affine:
                    counters["pruned"] += 1
                    return None
    reads = sum(plmod.dag_external_reads(fdag).values())
    out_w = plmod.output_words(sub_pipe)
    seconds = stream_seconds(reads + out_w)
    # time ratio: most conservative terminal schedule of the kernel
    # (pipe/seq < 1 is overlap speedup, > 1 exposed-latency slowdown)
    ratios = []
    for t in fdag.patterns:
        mp = build_schedule(t, budget_words, depth=depth)
        if mp is not None:
            body_words = sum(s.words for s in mp.stages
                             if s.kind in ("body", "compute"))
            seq, pipe, _ = model_speedup(
                mp, flops_per_body=body_words * 100.0)
            if seq > 0 and pipe > 0:
                ratios.append(pipe / seq)
    if ratios:
        seconds *= max(ratios)
    steps = int(fdag.grid)
    calibrated = calibrate.predicted_seconds(
        "Pipeline", seconds * HBM_BYTES_PER_S, steps, profile=profile)
    return (reads + out_w, mem.total_bytes, seconds, calibrated, steps)


def _price_whole_pipeline(pipe, *, vmem_budget: int, align: int,
                          max_points: int, profile,
                          counters: Dict[str, int],
                          depths: Tuple[int, ...] = DEPTHS) -> List[Tuple]:
    """Every feasible fully fused (block, depth) candidate, priced and
    sorted best-first (the analytic shortlist of the whole DAG).
    Entries are ``((block, depth), (words, vmem, s_ana, s_cal, steps))``;
    ties in calibrated seconds break toward the shallowest depth."""
    n_stages = len(plmod.topo_stages(pipe))
    try:
        whole = plmod.sub_pipeline(pipe, 0, n_stages)
    except (ValueError, NotImplementedError):
        return []
    priced = []
    for b in _pipeline_candidates(pipe, align, max_points):
        for d in depths:
            res = _price_pipeline_group(whole, b, vmem_budget=vmem_budget,
                                        profile=profile, counters=counters,
                                        depth=d)
            if res is not None:
                priced.append(((b, d), res))
    priced.sort(key=lambda t: (t[1][0], t[1][3], t[0][1], -t[1][1]))
    return priced


def measured_pipeline_shortlist(pipe, *,
                                top_k: int = TOP_K,
                                vmem_budget: int = VMEM_BYTES,
                                align: int = MXU,
                                max_points: int = MAX_POINTS,
                                profile=None,
                                timing_db=None,
                                warmup: int = MEASURE_WARMUP,
                                repeat: int = MEASURE_REPEAT,
                                calibrate_update: bool = True,
                                depths: Tuple[int, ...] = DEPTHS,
                                policy: Optional[resilience.Policy]
                                = None,
                                cache: Union[None, bool, str,
                                             TuningCache] = False
                                ) -> List[CandidateTiming]:
    """Hybrid step for a pipeline DAG: analytically shortlist fully
    fused (block, depth) candidates, lower the top-k whole megakernels
    (depth-deep rotating stage scratch included), time them, optionally
    fold the samples into the calibration profile.  ``policy``/``cache``
    mirror ``measured_shortlist``: deadline + retry per candidate,
    persistent quarantine when a cache is given."""
    s = _PipelineSpace(pipe, Options(vmem_budget=vmem_budget, align=align,
                                     max_points=max_points,
                                     depths=depths).resolved())
    priced = _price_whole_pipeline(
        pipe, vmem_budget=vmem_budget, align=align, max_points=max_points,
        profile=_resolve_profile(profile),
        counters={"explored": 0, "pruned": 0}, depths=depths)
    return _time_candidates(s, _timing_shortlist(s, priced, max(top_k, 1)),
                            timing_db=timing_db, warmup=warmup,
                            repeat=repeat, policy=policy,
                            cache=_resolve_cache(cache),
                            observe=calibrate_update)


def explore_pipeline(pipe, *,
                     vmem_budget: Optional[int] = None,
                     align: Optional[int] = None,
                     cache: Union[None, bool, str, TuningCache] = None,
                     max_points: Optional[int] = None,
                     measure: Optional[str] = None,
                     top_k: Optional[int] = None,
                     timing_db=None,
                     profile=None,
                     warmup: Optional[int] = None,
                     repeat: Optional[int] = None,
                     depths: Optional[Tuple[int, ...]] = None,
                     policy: Optional[resilience.Policy] = None,
                     bucketing: Optional[bool] = None,
                     options: Optional[Options] = None
                     ) -> PipelinePlan:
    """Joint design-space exploration for a pattern pipeline DAG.

    One tile candidate set is enumerated for the shared streaming
    domain (dtype-aware sublane alignment, ragged divisors) and crossed
    with the metapipeline buffer depths in ``depths`` (default
    ``DEPTHS = (2, 3, 4)``); each (block, depth) candidate prices the
    *fused* megakernel across the whole terminal set -- external
    traffic (fan-out tiles and stages charged once) plus metapipeline
    overlap and the DMA issue latency left exposed at that depth, with
    ``depth x`` VMEM charged per stage buffer and inter-stage traffic
    = 0 because intermediates live in the VMEM plan.  Ties in modeled
    seconds break toward the shallowest depth.  When no fused candidate
    fits VMEM the DAG is split into contiguous topological groups at
    the cheapest cuts, each group free to pick its *own* block size and
    depth (the split paths need not agree); every cut intermediate
    round-trips HBM.  The chosen per-group depths land in
    ``PipelinePlan.depths``.  Results are cached keyed on the
    topological DAG signature (+ device kind + calibration-profile
    hash + the resolved depth set).

    ``measure="top_k"`` (or ``REPRO_MEASURE=top_k``): when the analytic
    winner is fully fused, the top-k (block, depth) candidates are
    lowered as whole megakernels -- rotating depth-deep stage scratch
    included -- and timed; the measured argmin wins and the samples
    update the device calibration profile before the plan is cached.
    A split-fallback winner keeps the analytic choice (its groups
    execute as separate kernels; timing them jointly would conflate
    the cut traffic with tile effects).

    Measured candidates run under ``policy`` (deadline, transient
    retry), failures are quarantined in the tuning cache, and the
    measured winner must *certify* against the unfused per-stage
    oracle (``pipeline.run_unfused``) before promotion; when no
    candidate survives, the analytic plan ships and a fallback event
    is recorded -- candidate-level failures never raise.

    As in ``explore``, options may arrive packed in
    ``options=Options(...)`` (explicit kwarg > options > env > default)
    and ``bucketing=True`` enables bucketed warm starts: a cold
    ``shared_extent`` whose pipeline family has a tuned fused bucket is
    served an adapted plan immediately while a background re-tune
    promotes the certified exact-shape winner.
    """
    o = _resolve_options(options, vmem_budget=vmem_budget, align=align,
                         cache=cache, max_points=max_points,
                         measure=measure, top_k=top_k,
                         timing_db=timing_db, profile=profile,
                         warmup=warmup, repeat=repeat, depths=depths,
                         policy=policy, bucketing=bucketing)
    if o.trace:
        telemetry.enable()
    with telemetry.span("dse.explore_pipeline", pipeline=pipe.name) as sp:
        return _explore(_PipelineSpace(pipe, o), o, sp)


class _PipelineSpace:
    """The pipeline engine's search space: one streaming block x buffer
    depth over the fused DAG, split into contiguous topological groups
    by a prefix DP when no fused candidate fits.  Only a fully fused
    plan is one kernel: it alone is timed and lent as a bucket donor
    (a split plan's cuts are priced for one extent and its groups run
    as separate kernels)."""

    kind, tag = "pipeline", "pipe"   # bucket-index kind, re-tune tag
    plan_cls = PipelinePlan
    calib_kind = "Pipeline"
    bucketable = True

    def __init__(self, pipe, o: Options):
        self.pipe, self.o = pipe, o
        self.n_stages = len(plmod.topo_stages(pipe))
        self.cands = _pipeline_candidates(pipe, o.align, o.max_points)
        self.extra = (tuple(self.cands),) + _search_extra(o)
        # the search's accounting, mutated by its pricing calls
        self.counts = {"explored": 0, "pruned": 0}
        self.workload = f"Pipeline:{pipe.name}:{pipe.shared_extent}"

    def key(self, extra: Optional[Tuple] = None, **ctx) -> str:
        return pipeline_key(self.pipe, vmem_budget=self.o.vmem_budget,
                            align=self.o.align,
                            extra=self.extra if extra is None else extra,
                            **ctx)

    @functools.cached_property
    def unfused(self) -> int:
        return plmod.unfused_traffic_words(self.pipe)

    def search(self) -> Tuple[PipelinePlan, List[Tuple]]:
        o, pipe, n_stages = self.o, self.pipe, self.n_stages
        cands, depths, counters = self.cands, o.depths, self.counts
        prof = _resolve_profile(o.profile)
        # the fully fused (whole-range) candidates are priced once and
        # shared: they seed the DP's (0, n) entry AND the measured
        # shortlist (no duplicate fuse_dag/plan_memory work)
        with telemetry.span("dse.shortlist", pipeline=pipe.name) as ssp:
            priced_whole = _price_whole_pipeline(
                pipe, vmem_budget=o.vmem_budget, align=o.align,
                max_points=o.max_points, profile=prof, counters=counters,
                depths=depths)
            ssp.set(fused_candidates=len(priced_whole))

        def best_group(i0: int, i1: int, memo: Dict):
            """Per-group (block, depth) choice: cheapest (words, seconds,
            vmem, block, depth) for topo stages [i0, i1) over the
            candidate tiles crossed with the buffer depths (shallowest
            wins ties)."""
            if (i0, i1) in memo:
                return memo[(i0, i1)]
            best = None
            try:
                # built once per range: block-independent (validate /
                # topo analysis is not free, cands can be large)
                sub_pipe = plmod.sub_pipeline(pipe, i0, i1)
            except (ValueError, NotImplementedError):
                # e.g. a cut that makes a terminal both output and
                # consumed: this grouping is simply infeasible
                sub_pipe = None
            if sub_pipe is not None:
                for b in cands:
                    for d in depths:
                        priced = _price_pipeline_group(
                            sub_pipe, b, vmem_budget=o.vmem_budget,
                            profile=prof, counters=counters, depth=d)
                        if priced is None:
                            continue
                        rank = (priced[0], priced[3], d, -priced[1])
                        if best is None or rank < (best[0], best[1],
                                                   best[4], -best[2]):
                            best = (priced[0], priced[3], priced[1], b, d)
            memo[(i0, i1)] = best
            return best

        # prefix DP over contiguous topological groups; fewer groups
        # preferred on ties (the j == 0 single-group candidate is tried
        # first and later candidates must be strictly cheaper)
        memo: Dict = {}
        if priced_whole:
            (b, d), (words, vmem, _, s_cal, _) = priced_whole[0]
            memo[(0, n_stages)] = (words, s_cal, vmem, b, d)
        else:
            memo[(0, n_stages)] = None
        state: List = [None] * (n_stages + 1)
        # words, seconds, vmem, groups, blocks, depths
        state[0] = (0, 0.0, 0, (), (), ())
        for i in range(1, n_stages + 1):
            for j in range(0, i):
                if state[j] is None:
                    continue
                g = best_group(j, i, memo)
                if g is None:
                    continue
                cand = (state[j][0] + g[0], state[j][1] + g[1],
                        max(state[j][2], g[2]),
                        state[j][3] + ((j, i),), state[j][4] + (g[3],),
                        state[j][5] + (g[4],))
                if state[i] is None or (cand[0], cand[1]) \
                        < (state[i][0], state[i][1]):
                    state[i] = cand
        best = state[n_stages]
        if best is None:
            raise ValueError(
                "pipeline DSE: no tile candidate fits VMEM budget "
                f"{o.vmem_budget} B for '{pipe.name}' "
                f"({counters['explored']} candidates over {cands})")
        plan = PipelinePlan(
            block=int(best[4][0]), groups=best[3], group_blocks=best[4],
            traffic_words=int(best[0]), unfused_traffic_words=self.unfused,
            vmem_bytes=int(best[2]), modeled_seconds=float(best[1]),
            depths=best[5], **counters)
        return plan, priced_whole

    def point(self, c: Tuple) -> Tuple[PipelinePlan, float, int]:
        """A fused candidate ``((block, depth), (words, vmem, analytic
        s, calibrated s, steps))`` as (one-group plan, analytic seconds,
        grid steps)."""
        (b, d), (words, vmem, s_ana, s_cal, steps) = c
        return PipelinePlan(
            block=int(b), groups=((0, self.n_stages),),
            group_blocks=(int(b),), depths=(int(d),),
            traffic_words=int(words), unfused_traffic_words=self.unfused,
            vmem_bytes=int(vmem), modeled_seconds=float(s_cal),
            **self.counts), s_ana, steps

    def one_kernel(self, plan: PipelinePlan) -> bool:
        return plan.fused

    def ident(self, plan: PipelinePlan) -> Tuple:
        """Timing and certification identity: (block, depth).  Depth is
        part of it because the megakernel's rotating stage scratch is
        allocated depth-deep: depth variants are different
        executables."""
        return int(plan.block), int(plan.depth)

    def row(self, plan: PipelinePlan) -> Dict:
        return {"block": int(plan.block), "depth": int(plan.depth)}

    def timing_row(self, t: CandidateTiming) -> Dict:
        return {**self.row(t.plan),
                "median_s": float(t.measurement.median_s)}

    def sample_id(self, plan: PipelinePlan) -> str:
        return f"b={plan.block}d{plan.depth}"

    def lower(self, plan: PipelinePlan):
        from .codegen_pallas import lower_pipeline_for_timing
        return lower_pipeline_for_timing(
            self.pipe, plan, vmem_budget=self.o.vmem_budget), "pallas"

    def certify(self, plan: PipelinePlan) -> Tuple[bool, str]:
        return resilience.certify_pipeline_plan(
            self.pipe, plan, vmem_budget=self.o.vmem_budget)

    def retune(self, options: Options) -> PipelinePlan:
        return explore_pipeline(self.pipe, options=options)

    def span_attrs(self, plan: PipelinePlan) -> Dict:
        return {"groups": len(plan.groups)}

    def pruned_reasons(self, n_ranked: int, n_short: int, n_timed: int,
                       plan: PipelinePlan) -> Dict:
        return {"dominated": max(n_ranked - plan.timed, 0)
                if plan.timed else 0}

    def family(self) -> str:
        return buckets.pipeline_family(
            self.pipe, vmem_budget=self.o.vmem_budget, align=self.o.align)

    def bucket_domains(self) -> Dict[str, Tuple[int, ...]]:
        return buckets.pipeline_buckets(self.pipe)

    def refit(self, donor: PipelinePlan) -> Optional[PipelinePlan]:
        """A fully fused plan at ``donor``'s block re-fitted onto this
        extent's candidates and at its depth, priced uncalibrated
        (None: no fit)."""
        b = _fit(self.cands, donor.block)
        try:
            whole = plmod.sub_pipeline(self.pipe, 0, self.n_stages)
        except (ValueError, NotImplementedError):
            return None
        res = _price_pipeline_group(
            whole, b, vmem_budget=self.o.vmem_budget, profile=None,
            counters={"explored": 0, "pruned": 0}, depth=donor.depth)
        return None if res is None \
            else self.point(((b, donor.depth), res))[0]


# --------------------------------------------------------------------------
# Plan provenance: dse.explain
# --------------------------------------------------------------------------


def explain_dict(plan) -> Dict:
    """Machine-readable provenance report for a ``TilePlan`` /
    ``PipelinePlan``: where the winner came from (fresh exploration,
    tuning-cache hit, bucket warm start), what was enumerated and why
    candidates were rejected, the analytic and measured rankings and
    the certification outcomes.

    The deep exploration internals (rank tables, certification
    outcomes, per-reason pruning counts) are captured only while
    tracing is enabled (``REPRO_TRACE=1`` / ``Options(trace=True)``)
    and the plan was explored in this process; otherwise the report
    falls back to the accounting every plan carries on itself
    (explored/pruned totals, measured seconds, warm-start donor).
    """
    source = ("warm_start" if plan.warm_start
              else "cache" if plan.cached else "explored")
    d: Dict = {
        "kind": type(plan).__name__,
        "key": plan.key,
        "source": source,
        "explored": int(plan.explored),
        "pruned": int(plan.pruned),
        "traffic_words": int(plan.traffic_words),
        "vmem_bytes": int(plan.vmem_bytes),
        "modeled_seconds": float(plan.modeled_seconds),
        "measured": bool(plan.measured),
        "measured_seconds": float(plan.measured_seconds),
        "timed": int(plan.timed),
        "warm_start": bool(plan.warm_start),
        "bucket": plan.bucket,
        "cached": bool(plan.cached),
    }
    if isinstance(plan, PipelinePlan):
        d["block"] = int(plan.block)
        d["groups"] = [list(g) for g in plan.groups]
        d["depths"] = [int(x) for x in plan.depths]
    else:
        d["sizes"] = {k: list(v) for k, v in plan.sizes.items()}
        d["depths"] = {k: int(v) for k, v in plan.depths.items()}
        d["thinned"] = bool(plan.thinned)
    rec = telemetry.get_record("plan", plan.key) if plan.key else None
    if rec is not None:
        d["provenance"] = rec
        # the plan object's own warm_start flag is authoritative: the
        # background re-tune records its exploration under the same
        # key, but THIS plan is still the warm loan it was served as
        d["source"] = ("warm_start" if plan.warm_start
                       else rec.get("source", source))
    return d


def explain(plan) -> str:
    """Human-readable plan-provenance report (``explain_dict`` as
    text): winner source, tile/group choice, analytic vs measured
    ranks, per-reason pruning counts, certification outcomes."""
    d = explain_dict(plan)
    lines = [f"{d['kind']} {d['key'] or '<no key>'}",
             f"  source: {d['source']}"
             + (f" (bucket {d['bucket']})" if d["bucket"] else "")]
    if "sizes" in d:
        lines.append("  sizes: " + ", ".join(
            f"{k}={tuple(v)}" for k, v in sorted(d["sizes"].items())))
    else:
        lines.append(f"  block: {d['block']}  groups: {d['groups']}")
    lines.append(f"  depths: {d['depths']}")
    lines.append(f"  traffic: {d['traffic_words']} words   "
                 f"vmem: {d['vmem_bytes']} B   "
                 f"modeled: {d['modeled_seconds']:.3e} s")
    if d["measured"]:
        lines.append(f"  measured: {d['measured_seconds']:.3e} s "
                     f"({d['timed']} candidates timed)")
    lines.append(f"  enumerated: {d['explored']}  pruned: {d['pruned']}")
    rec = d.get("provenance")
    if rec:
        pr = rec.get("pruned")
        if isinstance(pr, dict):
            lines.append("  pruned by reason: " + ", ".join(
                f"{k}={v}" for k, v in sorted(pr.items())))
        for label, keyname in (("analytic ranks", "analytic_ranks"),
                               ("measured ranks", "measured_ranks")):
            rows = rec.get(keyname)
            if rows:
                lines.append(f"  {label}:")
                lines.extend(
                    f"    {i + 1}. " + ", ".join(f"{k}={v}"
                                                 for k, v in r.items())
                    for i, r in enumerate(rows))
        for c in rec.get("certification") or ():
            ident = ", ".join(f"{k}={v}" for k, v in c.items()
                              if k not in ("ok", "reason"))
            verdict = ("certified" if c.get("ok")
                       else f"FAILED ({c.get('reason', '')})")
            lines.append(f"  certify {ident}: {verdict}")
    else:
        lines.append("  (no in-process trace record; run with "
                     "REPRO_TRACE=1 for rank tables and pruning "
                     "reasons)")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# Proxy programs: PPL models of the Pallas kernels' loop structure.
# Bodies are only analyzed (traffic / memory / schedule), never executed,
# but are kept runnable for the codegen_jax oracle where cheap to do so.
# --------------------------------------------------------------------------


def attention_program(sq: int, sk: int, d: int) -> ir.Pattern:
    """Flash attention as Map(queries){ MultiFold(keys) } -- the online-
    softmax fold over keys nested in the query map (DESIGN.md §4).

    Tileable domains: ``fa_q`` (query block) and ``fa_kv`` (kv block).
    """
    import jax.numpy as jnp

    q = ir.Tensor("q", (sq, d))
    k = ir.Tensor("k", (sk, d))
    v = ir.Tensor("v", (sk, d))
    kv = ir.MultiFold(
        domain=(sk,), range_shape=(d,),
        init=lambda: jnp.zeros((d,)),
        reads=(ir.Access(q, lambda i, kk: (i, 0), (1, d)),
               ir.Access(k, lambda i, kk: (kk, 0), (1, d)),
               ir.Access(v, lambda i, kk: (kk, 0), (1, d))),
        out_index_map=lambda i, kk: (0,), update_shape=(d,),
        fn=lambda s, acc, qe, ke, ve: acc + jnp.sum(qe * ke) * ve,
        combine=lambda a, b: a + b, name="fa_kv")
    return ir.Map(domain=(sq,), elem_shape=(d,), inner=kv, name="fa_q")


def scan_program(seq: int, n: int, dh: int) -> ir.Pattern:
    """The SSD chunked scan's sequence fold: per step read an x row, a
    dt scalar and B/C rows, update the carried (n, dh) state.

    Tileable domain: ``ssd`` (the chunk length).
    """
    import jax.numpy as jnp

    x = ir.Tensor("x", (seq, dh))
    dt = ir.Tensor("dt", (seq,))
    B = ir.Tensor("B", (seq, n))
    C = ir.Tensor("C", (seq, n))
    return ir.MultiFold(
        domain=(seq,), range_shape=(n, dh),
        init=lambda: jnp.zeros((n, dh)),
        reads=(ir.Access(x, lambda i: (i, 0), (1, dh)),
               ir.elem(dt),
               ir.Access(B, lambda i: (i, 0), (1, n)),
               ir.Access(C, lambda i: (i, 0), (1, n))),
        out_index_map=lambda i: (0, 0), update_shape=(n, dh),
        fn=lambda s, acc, xe, dte, be, ce: acc + jnp.outer(be, xe) * dte,
        combine=lambda a, b: a + b, name="ssd")


def filter_reduce_program(t: int) -> ir.Pattern:
    """TPC-H Q6 shape: fused filter + weighted-sum fold over one stream
    (tileable domain: ``fr``)."""
    import jax.numpy as jnp

    x = ir.Tensor("x", (t,))
    w = ir.Tensor("w", (t,))
    return ir.MultiFold(
        domain=(t,), range_shape=(), init=lambda: jnp.zeros(()),
        reads=(ir.elem(x), ir.elem(w)),
        out_index_map=lambda i: (), update_shape=(),
        fn=lambda s, acc, xe, we: acc + xe * we,
        combine=lambda a, b: a + b, name="fr")


def groupby_program(t: int, num_keys: int, ew: int) -> ir.Pattern:
    """Keyed fold over a (t,) stream into a dense (num_keys, ew)
    accumulator (tileable domain: ``gbf``)."""
    import jax.numpy as jnp

    keys = ir.Tensor("keys", (t,), "int32")
    vals = ir.Tensor("vals", (t, ew))
    return ir.GroupByFold(
        domain=(t,), num_keys=num_keys, elem_shape=(ew,),
        init=lambda: jnp.zeros((num_keys, ew)),
        reads=(ir.elem(keys),
               ir.Access(vals, lambda i: (i, 0), (1, ew))),
        fn=lambda s, ke, ve: (ke.astype("int32"), ve),
        combine=lambda a, b: a + b, name="gbf")


def gemm_program(m: int, n: int, k: int) -> ir.Pattern:
    """The Table-3 GEMM (from the benchmark suite builders)."""
    from repro.patterns.analytics import gemm
    p, _, _, _ = gemm(m, n, k)
    return p


# --------------------------------------------------------------------------
# Kernel-facing block-size selection (one entry point per Pallas kernel)
# --------------------------------------------------------------------------


def _one(plan: TilePlan, name: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in plan.sizes[name])


def select_gemm_blocks(m: int, n: int, k: int, *,
                       vmem_budget: Optional[int] = None,
                       align: Optional[int] = None,
                       cache: Union[None, bool, str, TuningCache] = None,
                       measure: Optional[str] = None,
                       policy: Optional[resilience.Policy] = None,
                       options: Optional[Options] = None
                       ) -> Tuple[Tuple[int, int, int], TilePlan]:
    plan = explore(gemm_program(m, n, k), vmem_budget=vmem_budget,
                   align=align, cache=cache, measure=measure,
                   policy=policy, options=options)
    (bm, bn), (bk,) = _one(plan, "gemm"), _one(plan, "gemm_k")
    return (bm, bn, bk), plan


def select_attention_blocks(sq: int, sk: int, d: int, *,
                            vmem_budget: Optional[int] = None,
                            align: Optional[int] = None,
                            cache: Union[None, bool, str, TuningCache] = None,
                            measure: Optional[str] = None,
                            policy: Optional[resilience.Policy] = None,
                            options: Optional[Options] = None
                            ) -> Tuple[Tuple[int, int], TilePlan]:
    plan = explore(attention_program(sq, sk, d), vmem_budget=vmem_budget,
                   align=align, cache=cache, measure=measure,
                   policy=policy, options=options)
    (bq,), (bk,) = _one(plan, "fa_q"), _one(plan, "fa_kv")
    return (bq, bk), plan


def select_scan_blocks(seq: int, n: int, dh: int, *,
                       vmem_budget: Optional[int] = None,
                       align: Optional[int] = None,
                       cache: Union[None, bool, str, TuningCache] = None,
                       measure: Optional[str] = None,
                       policy: Optional[resilience.Policy] = None,
                       options: Optional[Options] = None
                       ) -> Tuple[int, TilePlan]:
    plan = explore(scan_program(seq, n, dh), vmem_budget=vmem_budget,
                   align=align, cache=cache, measure=measure,
                   policy=policy, options=options)
    (chunk,) = _one(plan, "ssd")
    return chunk, plan


def select_filter_reduce_blocks(t: int, *,
                                vmem_budget: Optional[int] = None,
                                align: Optional[int] = None,
                                cache: Union[None, bool, str,
                                             TuningCache] = None,
                                measure: Optional[str] = None,
                                policy: Optional[resilience.Policy]
                                = None,
                                options: Optional[Options] = None
                                ) -> Tuple[int, TilePlan]:
    plan = explore(filter_reduce_program(t), vmem_budget=vmem_budget,
                   align=align, cache=cache, measure=measure,
                   policy=policy, options=options)
    (bt,) = _one(plan, "fr")
    return bt, plan


def select_groupby_blocks(t: int, num_keys: int, ew: int, *,
                          vmem_budget: Optional[int] = None,
                          align: Optional[int] = None,
                          cache: Union[None, bool, str, TuningCache] = None,
                          measure: Optional[str] = None,
                          policy: Optional[resilience.Policy] = None,
                          options: Optional[Options] = None
                          ) -> Tuple[int, TilePlan]:
    plan = explore(groupby_program(t, num_keys, ew),
                   vmem_budget=vmem_budget, align=align, cache=cache,
                   measure=measure, policy=policy, options=options)
    (bt,) = _one(plan, "gbf")
    return bt, plan


def filter_fold_pipeline(t: int):
    """TPC-H Q6 as a two-stage *pipeline*: a mask Map producing the
    per-record contribution, folded by a separate sum stage.  The fused
    lowering keeps the (t,) intermediate in VMEM scratch; the unfused
    lowering round-trips it through HBM (the quantity
    ``PipelinePlan.traffic_ratio`` reports)."""
    import jax.numpy as jnp

    from .pipeline import Pipeline

    x = ir.Tensor("x", (t,))
    w = ir.Tensor("w", (t,))
    mask = ir.Map(domain=(t,), reads=(ir.elem(x), ir.elem(w)),
                  fn=lambda s, xe, we: xe * we, name="ff_mask")
    total = ir.MultiFold(
        domain=(t,), range_shape=(), init=lambda: jnp.zeros(()),
        reads=(ir.elem(ir.Tensor("ff_mask", (t,))),),
        out_index_map=lambda i: (), update_shape=(),
        fn=lambda s, acc, v: acc + v,
        combine=lambda a, b: a + b, name="ff_sum")
    return Pipeline(name="filter_fold", stages=(mask, total))


def select_fused_filter_fold_blocks(
        t: int, *, vmem_budget: Optional[int] = None,
        align: Optional[int] = None,
        cache: Union[None, bool, str, TuningCache] = None,
        measure: Optional[str] = None,
        policy: Optional[resilience.Policy] = None,
        options: Optional[Options] = None
        ) -> Tuple[int, PipelinePlan]:
    """Joint-DSE streaming tile for the fused filter+fold megakernel."""
    plan = explore_pipeline(filter_fold_pipeline(t),
                            vmem_budget=vmem_budget, align=align,
                            cache=cache, measure=measure, policy=policy,
                            options=options)
    return plan.block, plan


def select_fused_kmeans_blocks(
        n: int, k: int, d: int, *, vmem_budget: Optional[int] = None,
        align: Optional[int] = None,
        cache: Union[None, bool, str, TuningCache] = None,
        measure: Optional[str] = None,
        policy: Optional[resilience.Policy] = None,
        options: Optional[Options] = None
        ) -> Tuple[int, PipelinePlan]:
    """Joint-DSE streaming tile for the fused k-means DAG megakernel
    (assign -> {scatter-sum, count}; one plan for the whole DAG, cached
    on its topological signature)."""
    from repro.patterns.analytics import kmeans_pipeline
    pipe, _, _ = kmeans_pipeline(n, k, d)
    plan = explore_pipeline(pipe, vmem_budget=vmem_budget, align=align,
                            cache=cache, measure=measure, policy=policy,
                            options=options)
    return plan.block, plan


# --------------------------------------------------------------------------
# Paged serving decode: layout x page_size x block as joint DSE axes
# --------------------------------------------------------------------------

PAGED_LAYOUTS = ("split", "fused")   # split K/V pools vs head-interleaved
PAGE_SIZES = (8, 16, 32, 64)


def paged_decode_pipeline(max_len: int, page_size: int, d: int,
                          layout: str = "split"):
    """One decode step as the ``decode_attention`` pipeline DAG: a
    KV-append producer Map (merge the step's token at the ``seq_len``
    slot) feeding a flash-attention MultiFold terminal, over a *ragged*
    streaming domain (``ir.RaggedExtent``): the static extent is the
    page-padded context bound, the live extent the runtime ``seq_len``
    scalar, masked at page granularity.

    ``layout`` picks the KV stream shape the candidate prices:
    ``split`` streams separate K and V rows through two producer
    stages; ``fused`` streams one head-interleaved ``2d`` row through a
    single stage (half the streams, double the row width) -- same total
    words, different stream count / stage structure, which is exactly
    what the metapipeline model differentiates.
    """
    import jax.numpy as jnp

    from .pipeline import Pipeline

    if layout not in PAGED_LAYOUTS:
        raise ValueError(f"layout {layout!r}; one of {PAGED_LAYOUTS}")
    padded = -(-max_len // page_size) * page_size
    rag = ir.RaggedExtent(max=padded, length_name="seq_len",
                          granularity=page_size)
    q = ir.Tensor("q", (1, d))
    seq_len = ir.Tensor("seq_len", (1,), "int32")
    scale = d ** -0.5

    def append_fn(s, pagerow, new, ln):
        pagerow = jnp.reshape(pagerow, (-1,))
        new = jnp.reshape(new, (-1,))
        return jnp.where(s[0] == jnp.reshape(ln, ()), new, pagerow)

    if layout == "fused":
        pages = ir.Tensor("kv_pages", (padded, 2 * d))
        new_kv = ir.Tensor("new_kv", (1, 2 * d))
        append = ir.Map(
            domain=(padded,), elem_shape=(2 * d,),
            reads=(ir.Access(pages, lambda i: (i, 0), (1, 2 * d)),
                   ir.whole(new_kv), ir.whole(seq_len)),
            fn=append_fn, name="pd_append", ragged=rag)

        def fold_fn(s, acc, kvrow, qv, ln):
            kvrow = jnp.reshape(kvrow, (-1,))
            qv = jnp.reshape(qv, (-1,))
            w = jnp.where(s[0] <= jnp.reshape(ln, ()),
                          jnp.exp(jnp.sum(qv * kvrow[:d]) * scale), 0.0)
            return acc + w * kvrow[d:]

        fold = ir.MultiFold(
            domain=(padded,), range_shape=(d,),
            init=lambda: jnp.zeros((d,)),
            reads=(ir.Access(ir.Tensor("pd_append", (padded, 2 * d)),
                             lambda i: (i, 0), (1, 2 * d)),
                   ir.whole(q), ir.whole(seq_len)),
            out_index_map=lambda i: (0,), update_shape=(d,),
            fn=fold_fn, combine=lambda a, b: a + b, name="pd_kv",
            ragged=rag)
        return Pipeline(name="paged_decode_fused",
                        stages=(append, fold))

    k_pages = ir.Tensor("k_pages", (padded, d))
    v_pages = ir.Tensor("v_pages", (padded, d))
    new_k = ir.Tensor("new_k", (1, d))
    new_v = ir.Tensor("new_v", (1, d))
    app_k = ir.Map(
        domain=(padded,), elem_shape=(d,),
        reads=(ir.Access(k_pages, lambda i: (i, 0), (1, d)),
               ir.whole(new_k), ir.whole(seq_len)),
        fn=append_fn, name="pd_append_k", ragged=rag)
    app_v = ir.Map(
        domain=(padded,), elem_shape=(d,),
        reads=(ir.Access(v_pages, lambda i: (i, 0), (1, d)),
               ir.whole(new_v), ir.whole(seq_len)),
        fn=append_fn, name="pd_append_v", ragged=rag)

    def fold_fn_split(s, acc, krow, vrow, qv, ln):
        krow = jnp.reshape(krow, (-1,))
        vrow = jnp.reshape(vrow, (-1,))
        qv = jnp.reshape(qv, (-1,))
        w = jnp.where(s[0] <= jnp.reshape(ln, ()),
                      jnp.exp(jnp.sum(qv * krow) * scale), 0.0)
        return acc + w * vrow

    fold = ir.MultiFold(
        domain=(padded,), range_shape=(d,),
        init=lambda: jnp.zeros((d,)),
        reads=(ir.Access(ir.Tensor("pd_append_k", (padded, d)),
                         lambda i: (i, 0), (1, d)),
               ir.Access(ir.Tensor("pd_append_v", (padded, d)),
                         lambda i: (i, 0), (1, d)),
               ir.whole(q), ir.whole(seq_len)),
        out_index_map=lambda i: (0,), update_shape=(d,),
        fn=fold_fn_split, combine=lambda a, b: a + b, name="pd_kv",
        ragged=rag)
    return Pipeline(name="paged_decode_split",
                    stages=(app_k, app_v, fold))


def select_paged_decode_blocks(
        max_len: int, d: int, *, vmem_budget: Optional[int] = None,
        align: Optional[int] = None,
        cache: Union[None, bool, str, TuningCache] = None,
        measure: Optional[str] = None,
        policy: Optional[resilience.Policy] = None,
        options: Optional[Options] = None
        ) -> Tuple[Tuple[str, int, int, int], TilePlan]:
    """Joint search over KV layout x page size x streaming block x
    metapipeline depth for the fused paged-decode kernel.

    Every (layout, page_size) pair prices its own ``decode_attention``
    proxy DAG through ``explore_pipeline`` (block x depth inside, with
    the pipeline tuning cache and -- via ``options.bucketing`` -- the
    shape-bucket warm-start layer, bucketed on the padded max length);
    the argmin on modeled seconds wins.  Returns ``((layout,
    page_size, block, depth), plan)`` with ``plan`` a summary
    ``TilePlan`` whose provenance records the searched joint axes:
    ``sizes["pd_kv"]`` the streaming block, ``sizes["pd_page"]`` the
    page size, ``sizes["pd_layout"]`` the layout's ``PAGED_LAYOUTS``
    index, ``depths["pd_kv"]`` the buffer depth.
    """
    page_sizes = [p for p in PAGE_SIZES if p <= max(max_len, PAGE_SIZES[0])]
    best = None
    explored = pruned = timed = 0
    for layout in PAGED_LAYOUTS:
        for ps in page_sizes:
            pipe = paged_decode_pipeline(max_len, ps, d, layout)
            plan = explore_pipeline(pipe, vmem_budget=vmem_budget,
                                    align=align, cache=cache,
                                    measure=measure, policy=policy,
                                    options=options)
            explored += plan.explored
            pruned += plan.pruned
            timed += plan.timed
            if best is None or (plan.modeled_seconds
                                < best[2].modeled_seconds):
                best = (layout, ps, plan)
    layout, ps, pplan = best
    summary = TilePlan(
        sizes={"pd_kv": (int(pplan.block),), "pd_page": (int(ps),),
               "pd_layout": (PAGED_LAYOUTS.index(layout),)},
        traffic_words=pplan.traffic_words,
        vmem_bytes=pplan.vmem_bytes,
        modeled_seconds=pplan.modeled_seconds,
        explored=explored, pruned=pruned,
        cached=pplan.cached, measured=pplan.measured,
        measured_seconds=pplan.measured_seconds, timed=timed,
        depths={"pd_kv": int(pplan.depth)},
        warm_start=pplan.warm_start, bucket=pplan.bucket,
        key=pplan.key)
    return (layout, int(ps), int(pplan.block), int(pplan.depth)), summary
