"""Measured timing harness for lowered kernels (hybrid DSE, ROADMAP
"price with measured Pallas timings").

The analytic cost model (``core.cost``) prices candidates in modeled
HBM seconds; this module supplies the *measured* side of the hybrid
analytic->measured exploration:

  * ``measure``    -- median-of-k wall time of a zero-arg callable with
    ``jax.block_until_ready`` on every call; the first ``warmup`` calls
    (compilation + autotuning) are executed but excluded, so reported
    seconds are steady-state, never compile time.
  * ``TimingDB``   -- persistent device-keyed measurement store living
    alongside the DSE tuning cache (``REPRO_TIMING_DB``, defaulting to
    a sibling of ``REPRO_DSE_CACHE``): a candidate timed once is never
    lowered or executed again on that device.
  * ``synth_inputs`` -- deterministic concrete arrays for a pattern's
    symbolic ``ir.Tensor`` inputs (timing needs values, not semantics).

On CPU the repo's Pallas kernels run in ``interpret=True`` mode, so
timings are interpreter steady-state costs -- honest *relative* prices
for ranking candidates, not TPU absolutes.  The DB key carries both the
device kind and the interpret flag, so interpreter medians can never
masquerade as compiled-TPU medians after a device change.
"""
from __future__ import annotations

import dataclasses
import os
import statistics
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from . import ir, resilience, telemetry


# --------------------------------------------------------------------------
# Device identity
# --------------------------------------------------------------------------


def device_kind() -> str:
    """Normalized device identity ("cpu", "tpu-v5-lite", ...) keying
    the timing DB and the calibration profile."""
    import jax
    d = jax.devices()[0]
    kind = d.device_kind or d.platform
    return str(kind).strip().lower().replace(" ", "-")


def interpret_mode() -> bool:
    """True when the repo's Pallas kernels run interpreted (on the
    CPU); mirrored into every timing-DB key."""
    from . import backend
    return backend.interpret()


# --------------------------------------------------------------------------
# The measurement itself
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Measurement:
    """Steady-state wall time of one callable on one device."""

    median_s: float
    mean_s: float
    min_s: float
    max_s: float
    repeat: int
    warmup: int
    device: str = "unknown"
    interpret: bool = True
    cached: bool = False   # served from the TimingDB, nothing executed

    @property
    def spread(self) -> float:
        """(max - min) / median -- the noise figure surfaced next to
        measured rows in the CI gate output."""
        return (self.max_s - self.min_s) / max(self.median_s, 1e-12)

    def to_json(self) -> Dict:
        return {"median_s": self.median_s, "mean_s": self.mean_s,
                "min_s": self.min_s, "max_s": self.max_s,
                "repeat": self.repeat, "warmup": self.warmup,
                "device": self.device, "interpret": self.interpret}

    @classmethod
    def from_json(cls, d: Dict) -> "Measurement":
        return cls(median_s=float(d["median_s"]),
                   mean_s=float(d["mean_s"]),
                   min_s=float(d["min_s"]), max_s=float(d["max_s"]),
                   repeat=int(d["repeat"]), warmup=int(d["warmup"]),
                   device=str(d.get("device", "unknown")),
                   interpret=bool(d.get("interpret", True)),
                   cached=True)


def measure(fn: Callable[[], object], *, warmup: int = 1,
            repeat: int = 5) -> Measurement:
    """Median-of-``repeat`` wall seconds of ``fn()``.

    Every call is fenced with ``jax.block_until_ready`` (async dispatch
    would otherwise time the enqueue, not the kernel).  The first
    ``warmup`` calls run but are *excluded* -- they absorb tracing,
    compilation and first-touch allocation, the costs the old
    ``benchmarks/run.py --reps=1`` path conflated with steady state.
    """
    import jax

    if repeat < 1:
        raise ValueError(f"repeat must be >= 1, got {repeat}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    # chaos hook: REPRO_FAULTS=time:<p> makes this measurement fail
    # deterministically so the quarantine path can be exercised
    resilience.inject("time", "measure.measure")
    with telemetry.span("measure.measure", warmup=warmup,
                        repeat=repeat) as sp:
        for _ in range(warmup):
            jax.block_until_ready(fn())
        times = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        m = Measurement(median_s=statistics.median(times),
                        mean_s=sum(times) / len(times),
                        min_s=min(times), max_s=max(times),
                        repeat=repeat, warmup=warmup,
                        device=device_kind(),
                        interpret=interpret_mode())
        sp.set(median_s=m.median_s, spread=m.spread)
    return m


# --------------------------------------------------------------------------
# Persistent timing DB
# --------------------------------------------------------------------------


def cache_sibling_path(name: str,
                       env_var: Optional[str] = None) -> str:
    """Shared path resolution for every persistent store (tuning
    cache, timing DB, calibration profile): the store's own env var if
    set, else a sibling of ``REPRO_DSE_CACHE`` (the stores persist
    together, e.g. under one CI cache key), else the XDG cache dir."""
    if env_var:
        env = os.environ.get(env_var)
        if env:
            return env
    dse_cache = os.environ.get("REPRO_DSE_CACHE")
    if dse_cache:
        return os.path.join(os.path.dirname(dse_cache) or ".", name)
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "repro", name)


# atomic JSON write, re-exported for back-compat (the crash-safe
# store layer in ``core.resilience`` owns the implementation now)
atomic_write_json = resilience.atomic_write_json


def default_db_path() -> str:
    return cache_sibling_path("timing_db.json", "REPRO_TIMING_DB")


class TimingDB:
    """On-disk measurement store keyed by (device, interpret, key).

    Same contract as the DSE ``TuningCache``: crash-safe checksummed
    JSON (``resilience.load_store``: a truncated or corrupt file is
    quarantined to ``<path>.corrupt`` with a warning and the DB
    rebuilds fresh), lock-protected read-modify-write on put, and the
    DB only ever accelerates re-exploration -- it is never a
    correctness dependency.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_db_path()
        self._data: Optional[Dict[str, Dict]] = None

    @staticmethod
    def full_key(key: str, *, device: Optional[str] = None,
                 interpret: Optional[bool] = None) -> str:
        device = device_kind() if device is None else device
        interp = interpret_mode() if interpret is None else interpret
        return f"{device}|interp={int(interp)}|{key}"

    def _load(self) -> Dict[str, Dict]:
        if self._data is None:
            self._data = resilience.load_store(self.path,
                                               label="timing DB")
        return self._data

    def get(self, key: str) -> Optional[Measurement]:
        d = self._load().get(self.full_key(key))
        if d is None:
            return None
        try:
            return Measurement.from_json(d)
        except (KeyError, TypeError, ValueError):
            return None

    def put(self, key: str, m: Measurement) -> None:
        mine = self._load()
        mine[self.full_key(key)] = m.to_json()

        def merge(data: Dict) -> None:
            data[self.full_key(key)] = m.to_json()

        # locked read-modify-write: a concurrent process's entries
        # survive this put (and land in our in-memory view)
        self._data = resilience.locked_update(
            self.path, merge, label="timing DB", prefix=".timing_db.")
        self._data.update(mine)

    def clear(self) -> None:
        self._data = {}
        try:
            os.unlink(self.path)
        except OSError:
            pass


def resolve_db(db) -> Optional[TimingDB]:
    """Resolve the ``timing_db`` option (the same convention
    ``dse.Options`` carries): ``None`` -> default on-disk DB,
    path/TimingDB -> that DB, ``False`` -> no persistence."""
    if db is False:
        return None
    if db is None:
        return TimingDB()
    if isinstance(db, str):
        return TimingDB(db)
    return db


# historical private name, kept for existing callers
_resolve_db = resolve_db


def timed(key: str, make_fn: Callable[[], Callable[[], object]], *,
          db=None, warmup: int = 1, repeat: int = 5) -> Measurement:
    """Measure ``make_fn()()`` under ``key``, memoized in the DB.

    ``make_fn`` is a *thunk returning the callable*: on a DB hit
    nothing is built, so a cache-warm exploration does zero lowering
    and zero execution.
    """
    tdb = _resolve_db(db)
    if tdb is not None:
        hit = tdb.get(key)
        if hit is not None:
            telemetry.count("measure.db_hits")
            return hit
    with telemetry.span("measure.timed", key=key[-32:]) as sp:
        m = measure(make_fn(), warmup=warmup, repeat=repeat)
        sp.set(median_s=m.median_s)
    if tdb is not None:
        tdb.put(key, m)
    return m


# --------------------------------------------------------------------------
# Input synthesis
# --------------------------------------------------------------------------


def synth_inputs(tensors: Sequence[ir.Tensor], *, seed: int = 0
                 ) -> Dict[str, "np.ndarray"]:
    """Deterministic concrete arrays for symbolic pattern inputs.

    Timing only needs well-typed dense data: floats are standard
    normals, ints draw from a small non-negative range (safe for key
    tensors -- the CAM template's one-hot drops out-of-range keys
    rather than crashing).  Same seed -> bit-identical inputs, so DB
    entries from different sessions timed the same computation.
    """
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    out = {}
    for t in tensors:
        dt = np.dtype(t.dtype)
        shape = tuple(t.shape)
        if np.issubdtype(dt, np.integer):
            val = rng.integers(0, 8, size=shape).astype(dt)
        elif np.issubdtype(dt, np.bool_):
            val = rng.integers(0, 2, size=shape).astype(dt)
        else:
            val = rng.standard_normal(shape).astype(dt)
        out[t.name] = jnp.asarray(val)
    return out


def _rank(xs: Sequence[float]) -> Tuple[float, ...]:
    """Average ranks (ties averaged), 1-based."""
    order = sorted(range(len(xs)), key=lambda i: xs[i])
    ranks = [0.0] * len(xs)
    i = 0
    while i < len(order):
        j = i
        while j + 1 < len(order) and xs[order[j + 1]] == xs[order[i]]:
            j += 1
        avg = (i + j) / 2.0 + 1.0
        for k in range(i, j + 1):
            ranks[order[k]] = avg
        i = j + 1
    return tuple(ranks)


def spearman(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Spearman rank correlation (ties averaged).

    The quantity ``benchmarks/run.py --measure`` tables per workload:
    how well the (calibrated or uncalibrated) analytic candidate
    ranking matches the measured one.  Degenerate inputs (constant
    vectors, < 2 points) return 1.0 when the rankings trivially agree
    and 0.0 otherwise.
    """
    if len(xs) != len(ys):
        raise ValueError(f"length mismatch: {len(xs)} vs {len(ys)}")
    n = len(xs)
    if n < 2:
        return 1.0
    rx, ry = _rank(xs), _rank(ys)
    mx = sum(rx) / n
    my = sum(ry) / n
    cov = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    vx = sum((a - mx) ** 2 for a in rx)
    vy = sum((b - my) ** 2 for b in ry)
    if vx == 0 and vy == 0:
        return 1.0
    if vx == 0 or vy == 0:
        return 0.0
    return cov / (vx * vy) ** 0.5
