"""Analytic cost model: main-memory traffic and metapipeline overlap.

Reproduces the accounting of the paper's Fig. 5c ("minimum number of
words read from main memory and on-chip storage ... after each IR
transformation") and the metapipeline throughput model of §6.

Read model ("register promotion"): an access or tile copy is loaded
once per iteration of the loop nest *down to the deepest loop index it
depends on*; loops deeper than that reuse the buffered value.  A copy
with a constant base (``hoisted``) is loaded exactly once -- the Pipe-0
preload of Fig. 6.

Hardware constants come from one table of chips keyed by JAX's
``device_kind`` (``CHIPS``); the DSE plans for the TPU v5e wherever it
runs.  The FPGA numbers of the paper map to the same two-term structure
(compute vs. DRAM stream).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


from . import ir
from .affine import AffineMap


@dataclasses.dataclass(frozen=True)
class Chip:
    """Per-chip peaks the cost model and the roofline read."""

    hbm_bytes_per_s: float
    peak_flops: float      # bf16 matmul
    vmem_bytes: int        # scoped VMEM one Mosaic kernel may use


# Published peaks of one chip, from Google Cloud's "TPU v5e" page: 197
# TFLOP/s bf16, 16 GB of HBM at 819 GB/s.  ``vmem_bytes`` is Mosaic's
# default scoped-VMEM limit on that chip (the compiler's, not the page's).
CHIPS: Dict[str, Chip] = {
    "TPU v5 lite": Chip(hbm_bytes_per_s=819e9, peak_flops=197e12,
                        vmem_bytes=16 * 2 ** 20),
}
DSE_TARGET = "TPU v5 lite"   # the chip the DSE plans for, wherever it runs


def chip(kind: Optional[str] = None) -> Chip:
    """Peaks of ``kind``, by default of the device JAX runs on.  A CPU
    run plans against ``DSE_TARGET``; a device missing from ``CHIPS``
    is an error, never a default."""
    if kind is None:
        import jax
        d = jax.devices()[0]
        kind = DSE_TARGET if d.platform == "cpu" else d.device_kind
    if kind not in CHIPS:
        raise KeyError(f"no peaks for device kind {kind!r}; add the chip "
                       "to cost.CHIPS with its source")
    return CHIPS[kind]


HBM_BYTES_PER_S = CHIPS[DSE_TARGET].hbm_bytes_per_s
PEAK_FLOPS = CHIPS[DSE_TARGET].peak_flops
VMEM_BYTES = CHIPS[DSE_TARGET].vmem_bytes

# Fixed per-grid-step DMA cost (issue + flight latency) that bandwidth
# accounting misses: a tile's transfer can start at most ``depth - 1``
# outer iterations ahead of its consumer, so a metapipeline with
# buffer depth d hides up to ``(d - 1) x max_stage_seconds`` of it.
# What is left is the *exposed* latency ``metapipeline_time`` charges
# per steady-state step -- the quantity deeper buffering buys down.
DMA_ISSUE_LATENCY_S = 1e-6


@dataclasses.dataclass
class TrafficReport:
    """Main-memory words read per tensor + on-chip words per buffer."""

    reads: Dict[str, int]
    on_chip: Dict[str, int]

    @property
    def total_reads(self) -> int:
        return sum(self.reads.values())

    @property
    def total_on_chip(self) -> int:
        return sum(self.on_chip.values())


def _deepest_dep(amap: AffineMap) -> int:
    deps = amap.dependent_dims()
    return max(deps) if deps else -1


def _probe(index_map, n_in: int) -> Optional[AffineMap]:
    if isinstance(index_map, AffineMap):
        return index_map
    try:
        return AffineMap.probe(index_map, n_in)
    except Exception:
        return None  # non-affine


def _extent_of_dim(levels: List[Tuple[ir.Pattern, int]], dim: int) -> int:
    for p, off in levels:
        if off <= dim < off + len(p.domain):
            return p.domain[dim - off]
    raise KeyError(dim)


def _trips_to(levels: List[Tuple[ir.Pattern, int]], deepest: int) -> int:
    """Product of loop extents from the root down to ``deepest`` incl."""
    t = 1
    for p, off in levels:
        for j, e in enumerate(p.domain):
            if off + j <= deepest:
                t *= e
    return t


def traffic(p: ir.Pattern) -> TrafficReport:
    reads: Dict[str, int] = {}
    on_chip: Dict[str, int] = {}
    buf_idx = [0]

    def visit(q: ir.Pattern, levels):
        off = (levels[-1][1] + len(levels[-1][0].domain)) if levels else 0
        path = levels + [(q, off)]
        stack_len = off + len(q.domain)

        for tc in q.loads:
            if isinstance(tc.src, ir.Tensor):
                amap = _probe(tc.index_map, stack_len)
                if tc.hoisted or (amap is not None
                                  and not amap.dependent_dims()):
                    trips = 1
                else:
                    trips = _trips_to(path, _deepest_dep(amap))
                reads[tc.src.name] = (reads.get(tc.src.name, 0)
                                      + trips * tc.words // tc.reuse)
                on_chip[f"{tc.name}#{buf_idx[0]}"] = tc.words
            else:
                on_chip[f"{tc.name}#{buf_idx[0]}"] = tc.words
                visit(tc.src, path)
            buf_idx[0] += 1

        for a in q.accesses:
            if isinstance(a.src, ir.Tensor):
                amap = _probe(a.index_map, stack_len)
                if amap is None:  # non-affine: every iteration pays
                    trips = _trips_to(path, stack_len - 1)
                else:
                    deep = _deepest_dep(amap)
                    trips = _trips_to(path, deep) if deep >= 0 else 1
                reads[a.src.name] = (reads.get(a.src.name, 0)
                                     + trips * a.words)
                # untiled direct access still needs a window's worth of
                # registers/buffer (the paper's "d" for fused k-means)
                key = f"{a.src.name}_window"
                on_chip[key] = max(on_chip.get(key, 0), a.words)
            elif isinstance(a.src, ir.Pattern):
                visit(a.src, path)
        if q.inner is not None:
            visit(q.inner, path)

    visit(p, [])
    return TrafficReport(reads, on_chip)


# ------------------------------------------------------------------ time
@dataclasses.dataclass
class StageCost:
    name: str
    kind: str            # load | compute | store
    seconds: float


def metapipeline_time(stage_costs: List[StageCost],
                      outer_trips: int, depth: int = 2,
                      dma_latency_s: float = DMA_ISSUE_LATENCY_S
                      ) -> Tuple[float, float]:
    """(sequential, metapipelined) execution time for an outer loop whose
    body is the given stages.

    Sequential = sum per iteration; the metapipeline overlaps stages
    across outer iterations (buffers of depth >= 2), so steady-state
    cost = max stage (plus pipeline fill) plus the *exposed* DMA issue
    latency.  A buffer of depth ``d`` lets a load's DMA be issued up to
    ``d - 1`` iterations ahead, giving it ``(d - 1) x max_stage``
    seconds to land before its consumer needs it; whatever remains of
    ``dma_latency_s`` is charged once per steady-state step (issue
    latencies of concurrent loads overlap each other).  The term
    saturates at zero, so deepening past the point where latency is
    fully hidden buys nothing -- that is what keeps the DSE's optimum
    depth workload-dependent instead of "deeper is always better".
    """
    per_iter = [s.seconds for s in stage_costs]
    seq = outer_trips * sum(per_iter)
    step = max(per_iter)
    exposed = 0.0
    if any(s.kind == "load" for s in stage_costs):
        exposed = max(0.0, dma_latency_s - (max(depth, 1) - 1) * step)
    fill = sum(per_iter) - step
    pipe = fill + outer_trips * (step + exposed)
    return seq, pipe


def stage_seconds_load(words: int, bytes_per_word: int = 4,
                       bw: float = HBM_BYTES_PER_S) -> float:
    return words * bytes_per_word / bw


def stream_seconds(words: int, *, bytes_per_word: int = 4,
                   kind: str = "", steps: int = 1,
                   profile=None) -> float:
    """HBM stream seconds for ``words`` main-memory words.

    Uncalibrated (``profile=None``) this is the datasheet-bandwidth
    stream time every DSE pricing used before measured autotuning.
    With a ``calibrate.CalibrationProfile`` it becomes the *measured*
    prediction: effective tier bandwidth plus the per-pattern launch
    overhead paid once per kernel grid step -- the seam through which
    measured runs feed back into ``traffic``-based pricing.
    """
    if profile is None:
        return words * bytes_per_word / HBM_BYTES_PER_S
    from .calibrate import predicted_seconds
    return predicted_seconds(kind, words * bytes_per_word, steps,
                             profile=profile)


def stage_seconds_compute(flops: float,
                          peak: float = PEAK_FLOPS) -> float:
    return flops / peak


# ------------------------------------------------- serving decode traffic
def dense_decode_traffic_words(batch: int, cache_len: int, kv_heads: int,
                               head_dim: int) -> int:
    """Modeled HBM words one decode step streams through a *dense*
    (unpaged) KV cache: every request reads its full ``cache_len``
    extent of K and V regardless of how many tokens are live, plus the
    new token's K/V write and the query read."""
    kv = 2 * batch * cache_len * kv_heads * head_dim
    token = 2 * batch * kv_heads * head_dim      # K/V append
    q = batch * kv_heads * head_dim
    return kv + token + q


def paged_decode_traffic_words(seq_lens, page_size: int, kv_heads: int,
                               head_dim: int) -> int:
    """Modeled HBM words one decode step streams through the paged
    cache: each request touches only its live pages (``seq_len``
    rounded up to page granularity), so ragged batches stop paying for
    the longest request's extent.  Layouts (split vs. head-interleaved
    fused K/V) move the same words; they differ in stream *count*,
    which ``metapipeline_time`` prices, not in this total."""
    total = 0
    for ln in seq_lens:
        pages = -(-int(ln) // page_size)
        total += 2 * pages * page_size * kv_heads * head_dim
        total += 3 * kv_heads * head_dim         # K/V append + query
    return total
