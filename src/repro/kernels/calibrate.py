"""Calibration mode for roofline cost extraction + measured HW profiles.

XLA's ``cost_analysis()`` counts a ``while``-loop body once, not per trip,
so scanned graphs under-report FLOPs/bytes/collective traffic by their
trip counts. Under ``calibration()`` the chunked recurrences (SSD, WKV)
fully unroll their chunk scans so every chunk's work appears in the HLO —
this preserves the *production* chunk sizes, i.e. the linear-in-S compute
profile, unlike simply setting chunk=S (which would be quadratic).

On top of that mode this module builds the **calibration pass** (DESIGN.md
§17): microbenchmark the four kernel families (``gemm``,
``flash_attention``, ``rwkv6``, ``ssm_scan``) on the host backend, read
their trip-exact FLOP/byte counts from ``cost_analysis()`` under
``calibration()``, time the production-compiled executables, and fit the
analytical evaluator's constants into a versioned :class:`CalibratedHW`
profile.  The profile is persisted with the ``serve/cache_store`` record
framing (magic + schema header, CRC-framed records, atomic save), so a
stale or corrupt profile degrades to a cold re-calibration, never a crash.

Fitting contract
----------------
``flops_per_s``   achieved matmul throughput (gemm samples only — the
                  eq.-7 systolic model is a matmul model).  Applied as
                  ``freq_hz = flops_per_s / (2·R·C)`` so R·C·2·freq
                  reproduces the measured peak, mirroring
                  ``sharding/mcm_planner.tpu_hw``.
``bytes_per_s``   achieved HLO-byte streaming rate (best over all
                  samples) — the unit the dryrun cost-analysis side of
                  the validation gate also reports, so predicted and
                  measured roofline terms share a basis.
``byte_overhead`` median HLO-bytes / ideal-bytes (operand+result element
                  counts × dtype size) across samples, clipped ≥ 1.  The
                  evaluator traffics *ideal* bytes, so its effective
                  memory bandwidth is ``bytes_per_s / byte_overhead``.
``nop_frac``      NoP-link : memory bandwidth ratio.  One host exposes no
                  inter-chip fabric, so this architectural ratio is kept
                  from the v5e datasheet (ICI / HBM) rather than fitted —
                  documented, not hidden.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import statistics
import time

_CAL = contextvars.ContextVar("kernel_calibration", default=False)

# Profile schema — bump when CalibratedHW fields change meaning; old
# profiles then miss on the versioned key and trigger re-calibration.
PROFILE_SCHEMA = 1

# v5e ICI link (50 GB/s) : HBM (819 GB/s) — architectural ratio used for
# bw_nop when calibrating on a host with no measurable interconnect.
ICI_OVER_HBM = 50e9 / 819e9


@contextlib.contextmanager
def calibration(on: bool = True):
    tok = _CAL.set(on)
    try:
        yield
    finally:
        _CAL.reset(tok)


def scan_unroll():
    """unroll= argument for inner lax.scans: full unroll when calibrating."""
    return True if _CAL.get() else 1


# --------------------------------------------------------------------------
# Measured samples and the fitted profile
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSample:
    """One microbenchmark point: trip-exact HLO counts + wall clock."""
    kernel: str                # gemm | flash_attention | rwkv6 | ssm_scan
    shape: tuple               # human-readable problem dims
    flops: float               # HLO FLOPs under calibration() (trip-exact)
    hlo_bytes: float           # HLO bytes accessed under calibration()
    ideal_bytes: float         # operand+result elements × dtype size
    wall_s: float              # median production-executable wall clock
    reps: int = 1

    @property
    def achieved_flops_per_s(self) -> float:
        return self.flops / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def achieved_bytes_per_s(self) -> float:
        return self.hlo_bytes / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def byte_overhead(self) -> float:
        return (self.hlo_bytes / self.ideal_bytes
                if self.ideal_bytes > 0 else 1.0)


@dataclasses.dataclass(frozen=True)
class CalibratedHW:
    """Fitted evaluator constants for one backend (see module docstring)."""
    backend: str
    flops_per_s: float         # per model chip, matmul-achieved
    bytes_per_s: float         # per model chip, HLO-byte basis
    byte_overhead: float       # HLO bytes per ideal byte (≥ 1)
    nop_frac: float = ICI_OVER_HBM
    schema: int = PROFILE_SCHEMA
    samples: tuple = ()

    def freq_for(self, R: int, C: int) -> float:
        """Systolic clock reproducing the measured matmul peak: the eq.-7
        model delivers R·C·2·freq FLOP/s per chiplet."""
        return self.flops_per_s / (2.0 * R * C)

    @property
    def bw_mem_model(self) -> float:
        """Effective memory bandwidth on the evaluator's ideal-byte basis."""
        return self.bytes_per_s / max(self.byte_overhead, 1.0)

    @property
    def bw_nop_model(self) -> float:
        """Per-link NoP bandwidth: architectural ratio × measured memory."""
        return self.bw_mem_model * self.nop_frac

    def apply(self, hw) -> "HWConfig":  # noqa: F821 - forward ref
        """Rescale an :class:`~repro.core.hw.HWConfig` onto the measured
        constants: every chiplet owns one calibrated memory port (the
        type-C / pod mapping of ``sharding/mcm_planner``)."""
        n_chips = hw.X * hw.Y
        return hw.replace(
            freq_hz=self.freq_for(hw.R, hw.C),
            bw_mem=self.bw_mem_model * n_chips,
            bw_nop=self.bw_nop_model)


# --------------------------------------------------------------------------
# Microbenchmarks (host-backend XLA paths; Pallas interpret mode is far
# too slow off-TPU to time honestly)
# --------------------------------------------------------------------------

def _measure(fn, args, *, reps: int) -> tuple[float, float, float]:
    """(calib_flops, calib_bytes, median_wall_s) for fn(*args).

    Counts come from the calibration()-unrolled lowering so scanned
    kernels report per-trip work; timing runs the production executable
    (rolled scans) — both execute the same arithmetic.
    """
    import jax

    from ..launch.dryrun import cost_analysis_dict

    with calibration():
        calib = jax.jit(fn).lower(*args).compile()
    cd = cost_analysis_dict(calib)
    flops = float(cd.get("flops", 0.0))
    nbytes = float(cd.get("bytes accessed", 0.0))

    prod = jax.jit(fn).lower(*args).compile()
    out = prod(*args)                       # warm-up / ensure executable
    jax.block_until_ready(out)
    walls = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(prod(*args))
        walls.append(time.perf_counter() - t0)
    return flops, nbytes, statistics.median(walls)


def _nbytes(*arrays) -> float:
    return float(sum(a.size * a.dtype.itemsize for a in arrays))


def _bench_gemm(rng, sizes, reps) -> list[KernelSample]:
    import jax.numpy as jnp

    from .gemm.ref import matmul_ref

    out = []
    for m, k, n in sizes:
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        f, hb, w = _measure(matmul_ref, (a, b), reps=reps)
        ideal = _nbytes(a, b) + 4.0 * m * n
        out.append(KernelSample("gemm", (m, k, n), f, hb, ideal, w, reps))
    return out


def _bench_attention(rng, sizes, reps) -> list[KernelSample]:
    import jax.numpy as jnp

    from .flash_attention.blockwise import blockwise_attention

    out = []
    for bsz, s, h, dh in sizes:
        q = jnp.asarray(rng.standard_normal((bsz, s, h, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((bsz, s, h, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((bsz, s, h, dh)), jnp.float32)
        fn = lambda q, k, v: blockwise_attention(q, k, v, causal=True)
        f, hb, w = _measure(fn, (q, k, v), reps=reps)
        ideal = _nbytes(q, k, v) * 4.0 / 3.0    # q,k,v + same-shaped out
        out.append(KernelSample("flash_attention", (bsz, s, h, dh),
                                f, hb, ideal, w, reps))
    return out


def _bench_rwkv6(rng, sizes, reps) -> list[KernelSample]:
    import jax.numpy as jnp

    from .rwkv6.chunked import wkv6_chunked

    out = []
    for bsz, s, h, k, chunk in sizes:
        shp = (bsz, s, h, k)
        r = jnp.asarray(rng.standard_normal(shp), jnp.float32)
        kk = jnp.asarray(rng.standard_normal(shp), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shp), jnp.float32)
        w = jnp.asarray(rng.uniform(0.6, 0.99, shp), jnp.float32)
        u = jnp.asarray(rng.standard_normal((h, k)), jnp.float32)
        fn = lambda r, kk, v, w, u: wkv6_chunked(r, kk, v, w, u,
                                                 chunk=chunk)[0]
        f, hb, wall = _measure(fn, (r, kk, v, w, u), reps=reps)
        ideal = _nbytes(r, kk, v, w, u) + _nbytes(r)   # out ~ r-shaped
        out.append(KernelSample("rwkv6", (bsz, s, h, k, chunk),
                                f, hb, ideal, wall, reps))
    return out


def _bench_ssm_scan(rng, sizes, reps) -> list[KernelSample]:
    import jax.numpy as jnp

    from .ssm_scan.chunked import ssm_scan_chunked

    out = []
    for bsz, s, h, p, g, n, chunk in sizes:
        x = jnp.asarray(rng.standard_normal((bsz, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (bsz, s, h)), jnp.float32)
        a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((bsz, s, g, n)), jnp.float32)
        C = jnp.asarray(rng.standard_normal((bsz, s, g, n)), jnp.float32)
        D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
        fn = lambda x, dt, a, B, C, D: ssm_scan_chunked(x, dt, a, B, C, D,
                                                        chunk=chunk)[0]
        f, hb, wall = _measure(fn, (x, dt, a, B, C, D), reps=reps)
        ideal = _nbytes(x, dt, a, B, C, D) + _nbytes(x)
        out.append(KernelSample("ssm_scan", (bsz, s, h, p, g, n, chunk),
                                f, hb, ideal, wall, reps))
    return out


def profile_kernels(*, smoke: bool = False, reps: int = 3,
                    seed: int = 0) -> CalibratedHW:
    """Run the kernel microbenchmarks and fit a :class:`CalibratedHW`."""
    import numpy as np

    import jax

    rng = np.random.default_rng(seed)
    if smoke:
        gemm_sizes = [(128, 128, 128), (256, 256, 256)]
        attn_sizes = [(1, 128, 2, 32)]
        rwkv_sizes = [(1, 64, 1, 16, 16)]
        ssm_sizes = [(1, 128, 1, 8, 1, 8, 32)]
    else:
        gemm_sizes = [(256, 256, 256), (512, 512, 512), (768, 768, 768)]
        attn_sizes = [(1, 256, 4, 64), (2, 512, 4, 64)]
        rwkv_sizes = [(1, 128, 2, 32, 32), (2, 256, 2, 32, 32)]
        ssm_sizes = [(1, 256, 2, 16, 1, 16, 64), (2, 512, 2, 16, 1, 16, 64)]

    samples: list[KernelSample] = []
    samples += _bench_gemm(rng, gemm_sizes, reps)
    samples += _bench_attention(rng, attn_sizes, reps)
    samples += _bench_rwkv6(rng, rwkv_sizes, reps)
    samples += _bench_ssm_scan(rng, ssm_sizes, reps)

    gemm = [s for s in samples if s.kernel == "gemm"]
    flops_per_s = max(s.achieved_flops_per_s for s in gemm)
    bytes_per_s = max(s.achieved_bytes_per_s for s in samples)
    overhead = max(1.0, statistics.median(
        s.byte_overhead for s in samples if s.ideal_bytes > 0))
    return CalibratedHW(
        backend=jax.default_backend(),
        flops_per_s=flops_per_s,
        bytes_per_s=bytes_per_s,
        byte_overhead=overhead,
        samples=tuple(samples))


# --------------------------------------------------------------------------
# Persistence — serve/cache_store record idiom (versioned key; corrupt or
# stale files degrade to a miss, never a crash)
# --------------------------------------------------------------------------

_PROFILE_KEY = ("calibrated_hw", PROFILE_SCHEMA)


def save_profile(profile: CalibratedHW, path: str) -> int:
    from ..serve.cache_store import CacheStore
    return CacheStore(path).save({_PROFILE_KEY: profile})


def load_profile(path: str) -> CalibratedHW | None:
    from ..serve.cache_store import CacheStore
    prof = CacheStore(path).load().get(_PROFILE_KEY)
    if isinstance(prof, CalibratedHW) and prof.schema == PROFILE_SCHEMA:
        return prof
    return None
