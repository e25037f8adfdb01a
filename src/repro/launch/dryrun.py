"""Multi-pod dry-run: ``lower().compile()`` every (architecture × input
shape) cell on the production meshes, proving the distribution config is
coherent — shardings lower, collectives are legal, and the per-device
memory fits — without any TPU hardware.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k --mesh both

Artifacts (memory analysis, cost analysis, per-collective byte counts) are
written to ``benchmarks/artifacts/dryrun/<mesh>/<arch>__<shape>.json`` and
consumed by the roofline benchmark (EXPERIMENTS.md §Dry-run/§Roofline).

Importing this module has no side effects. The CLI entrypoint calls
:func:`ensure_virtual_devices` itself (the production meshes need 512
host devices); library users pick their own topology — e.g. via
``benchmarks/common.py:force_host_devices`` — before first backend use.
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import SHAPE_DEFS, get_config, runnable_cells
from ..models import forward, init_caches, init_model
from ..sharding.logical import use_rules
from ..sharding.partition_specs import activation_rules
from ..train import adamw
from ..train.train_step import make_train_step
from .mesh import make_production_mesh
from .specs import cache_specs, input_specs, params_specs_only, state_specs

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

from .policies import TRAIN_ACCUM, TRAIN_LOWMEM, TRAIN_V_BF16  # noqa: E402

_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def ensure_virtual_devices(n: int = 512) -> None:
    """Carve the host into ``n`` virtual XLA devices unless the caller
    already pinned a count. Must run before jax initializes its backend —
    the CLI below calls it first thing; importing this module never does."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICES_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{_DEVICES_FLAG}={n} {flags}".strip()


_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\b")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|s64|u32|s8|u8|pred|s16|u16)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s64": 8,
          "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s16": 2, "u16": 2}


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty where the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum per-device output bytes of every collective op in the
    post-SPMD HLO module."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # first shape on the line is the op result type
        sm = _SHAPE_RE.search(line)
        if not sm:
            continue
        dt, dims = sm.group(1), sm.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] = out.get(kind, 0.0) + n * _BYTES[dt]
    return out


def _make_opt(arch):
    if arch in TRAIN_LOWMEM:
        v_dt = jnp.bfloat16 if arch in TRAIN_V_BF16 else jnp.float32
        return adamw(m_dtype=jnp.bfloat16, v_dtype=v_dt)
    return adamw()


def _step_fn(cfg, kind, accum: int = 1, arch: str = ""):
    if kind == "train":
        opt = _make_opt(arch)
        accum_dtype = jnp.bfloat16 if arch in TRAIN_LOWMEM else jnp.float32
        return make_train_step(cfg, opt, accum_steps=accum,
                               accum_dtype=accum_dtype)
    if kind == "prefill":
        def prefill(params, batch, caches):
            logits, new_caches, _ = forward(params, cfg, batch,
                                            mode="prefill", caches=caches)
            return logits, new_caches
        return prefill
    if kind == "decode":
        def decode(params, batch, caches, pos):
            logits, new_caches, _ = forward(params, cfg, batch,
                                            mode="decode", caches=caches,
                                            pos=pos)
            tok = jnp.argmax(logits[:, -1, : cfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            return tok, new_caches
        return decode
    raise ValueError(kind)


def lower_cell(arch: str, shape: str, mesh, *, shard_residual=None,
               extra_rules=None, accum=None, cfg_overrides=None,
               serve_fsdp=None, cfg=None):
    """Returns (lowered, meta) for one cell on one mesh. The keyword knobs
    (sharding rules, accumulation, config fields) are the §Perf iteration
    surface; ``cfg=`` substitutes an explicit config (e.g. the reduced
    variants) for the registry lookup."""
    if cfg is None:
        cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    sd = SHAPE_DEFS[shape]
    kind = sd["kind"]
    if cfg.is_encoder and shape == "prefill_32k":
        kind = "prefill_encoder"
    if shard_residual is None:
        # residual-stream sharding on for training of wide models
        shard_residual = kind == "train" and cfg.d_model >= 2048
    rules = activation_rules(mesh, shard_residual=shard_residual)
    if extra_rules:
        rules.update(extra_rules)

    # ≥100B models extend FSDP across pods (their state exceeds one pod).
    fsdp = ("pod", "data") if arch in TRAIN_LOWMEM else ("data",)
    if serve_fsdp is not None and kind != "train":
        fsdp = serve_fsdp            # e.g. () = replicated-params serving
    n_accum = accum if accum is not None else TRAIN_ACCUM.get(arch, 1)
    with use_rules(mesh, rules):
        if kind == "train":
            step = _step_fn(cfg, "train", n_accum, arch)
            state_abs, state_sh = state_specs(cfg, mesh,
                                              optimizer=_make_opt(arch),
                                              fsdp_axes=fsdp)
            batch = input_specs(cfg, shape, mesh)
            lowered = jax.jit(
                step, in_shardings=(state_sh, None),
                donate_argnums=(0,)).lower(state_abs, batch)
        elif kind in ("prefill", "prefill_encoder"):
            params_abs, params_sh = params_specs_only(cfg, mesh, fsdp)
            batch = input_specs(cfg, shape, mesh)
            if kind == "prefill_encoder" or cfg.is_encoder:
                def enc(params, b):
                    logits, _, _ = forward(params, cfg, b, mode="train")
                    return logits
                lowered = jax.jit(enc, in_shardings=(params_sh, None)
                                  ).lower(params_abs, batch)
            else:
                caches_abs, caches_sh = cache_specs(cfg, shape, mesh)
                step = _step_fn(cfg, "prefill")
                lowered = jax.jit(
                    step, in_shardings=(params_sh, None, caches_sh),
                    donate_argnums=(2,)).lower(params_abs, batch,
                                               caches_abs)
        else:  # decode
            params_abs, params_sh = params_specs_only(cfg, mesh, fsdp)
            batch = input_specs(cfg, shape, mesh)
            caches_abs, caches_sh = cache_specs(cfg, shape, mesh)
            step = _step_fn(cfg, "decode")
            lowered = jax.jit(
                step, in_shardings=(params_sh, None, caches_sh, None),
                donate_argnums=(2,)).lower(
                    params_abs, batch, caches_abs,
                    jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, {"arch": arch, "shape": shape, "kind": kind,
                     "cfg": cfg}


def perf_knobs(arch: str, shape: str) -> dict:
    """The beyond-paper layout changes adopted by EXPERIMENTS.md §Perf."""
    kind = SHAPE_DEFS[shape]["kind"]
    knobs: dict = {}
    if kind in ("prefill", "decode"):
        # replicated-params serving (29x on gemma2 decode) — safe whenever
        # the TP-sharded bf16 params fit comfortably (all but deepseek).
        if arch != "deepseek-v2-236b":
            knobs["serve_fsdp"] = ()
    if arch == "internlm2-20b" and kind == "train":
        knobs["shard_residual"] = False      # no ZeRO-R (2.0x)
        knobs["accum"] = 8
    if arch == "minicpm3-4b" and kind == "prefill":
        from jax.sharding import PartitionSpec as P
        knobs["extra_rules"] = {"attn_qchunk": P(("data",), "model",
                                                 None, None, None)}
    return knobs


def run_cell(arch: str, shape: str, mesh_name: str, mesh,
             calibrate: bool = False, **knobs) -> dict:
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape, mesh, **knobs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "kind": meta["kind"],
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_per_device": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_per_device": coll,
    }
    if calibrate:
        rec["calibrated"] = calibrate_cell(arch, shape, mesh, **knobs)
    return rec


def execute_plan(plan_result, arch: str, shape: str, mesh,
                 mesh_name: str = "plan", *, calibrate: bool = False,
                 cfg=None, cfg_overrides=None, extra_rules=None,
                 **extra_knobs) -> dict:
    """Lower, compile, and cost the layout a ``sharding/mcm_planner`` plan
    chose: the planner's knobs (residual-stream sharding, microbatch
    accumulation, redistribution mask) become executable dryrun knobs
    instead of a report. Returns a ``run_cell`` record with a ``plan``
    section recording the analytical prediction next to the measured cost
    analysis — the unit the validation gate compares."""
    knobs = plan_result.to_dryrun_knobs()
    knobs.update(extra_knobs)
    rec = run_cell(arch, shape, mesh_name, mesh, calibrate=calibrate,
                   cfg=cfg, cfg_overrides=cfg_overrides,
                   extra_rules=extra_rules, **knobs)
    rec["plan"] = {
        "arch": plan_result.arch,
        "baseline_latency_s": float(plan_result.baseline_latency),
        "optimized_latency_s": float(plan_result.optimized_latency),
        "modeled_speedup": float(plan_result.modeled_speedup),
        "nonuniform_headroom": float(plan_result.nonuniform_headroom),
        "redist_mask": [int(b) for b in plan_result.redist_mask],
        "knobs": {k: v for k, v in knobs.items()},
    }
    return rec


def _calib_layers(cfg) -> tuple[int, int, float, float, float]:
    """(L1, L2, units1, units2, units_full) for per-unit extrapolation."""
    if cfg.family == "hybrid":
        p = cfg.hybrid_attn_period
        return p, 2 * p, 1, 2, cfg.n_layers / p
    if cfg.local_global_period:
        p = cfg.local_global_period
        return p, 2 * p, 1, 2, cfg.n_layers / p
    if cfg.first_dense_layers:
        d = cfg.first_dense_layers
        return d + 1, d + 2, 1, 2, cfg.n_layers - d
    return 1, 2, 1, 2, cfg.n_layers


def calibrate_cell(arch: str, shape: str, mesh, *, extra_rules=None,
                   accum=None, shard_residual=None,
                   cfg_overrides=None, serve_fsdp=None, cfg=None) -> dict:
    """Exact per-cell roofline quantities: lower two small *unrolled*
    configs (single-trip inner scans via calibration mode, attention/loss
    chunks = S, accumulation loop unrolled) and extrapolate per repeating
    unit to full depth. See kernels/calibrate.py for why (while-loop cost
    counting). Accepts the same §Perf knobs as lower_cell."""
    from ..kernels.calibrate import calibration

    base = cfg if cfg is not None else get_config(arch)
    if cfg_overrides:
        base = base.replace(**cfg_overrides)
    L1, L2, u1, u2, uf = _calib_layers(base)

    # Linear-complexity archs (SSM/hybrid: chunked recurrences + windowed
    # attention) calibrate on a 4k slice of long sequences and scale —
    # fully unrolling 32k/Lc chunk bodies is compile-pathological, and
    # their per-token cost is constant beyond the window.
    sd = SHAPE_DEFS[shape]
    shape_used, seq_scale = shape, 1.0
    if (base.family in ("ssm", "hybrid") and sd["kind"] != "decode"
            and sd["seq_len"] > 8192):
        shape_used = f"__calib_{shape}"
        SHAPE_DEFS[shape_used] = dict(sd, seq_len=4096)
        seq_scale = sd["seq_len"] / 4096.0

    # Train cells: per-microbatch work (param re-gathers!) scales linearly
    # with accumulation, so calibrate at accum∈{1,2} and extrapolate
    # bilinearly in (layers, accum) — unrolling accum=8 microbatches would
    # be compile-pathological. Other kinds: accum is not a variable.
    af = accum if accum is not None else TRAIN_ACCUM.get(arch, 1)
    accums = (1, 2) if SHAPE_DEFS[shape]["kind"] == "train" else (None,)

    out = {}
    try:
        for ai, a in enumerate(accums):
            for li, L in enumerate((L1, L2)):
                cfg = base.replace(n_layers=L, scan_layers=False,
                                   attn_chunk=1_000_000_000,
                                   loss_chunk=1_000_000_000)
                with calibration():
                    lowered, _ = _lower_with_cfg(
                        cfg, arch, shape_used, mesh,
                        extra_rules=extra_rules, accum=a,
                        shard_residual=shard_residual,
                        serve_fsdp=serve_fsdp)
                compiled = lowered.compile()
                cost = cost_analysis_dict(compiled)
                out[ai, li] = {
                    "flops": float(cost.get("flops", 0.0)),
                    "bytes": float(cost.get("bytes accessed", 0.0)),
                    "coll": collective_bytes(compiled.as_text()),
                }
    finally:
        if shape_used != shape:
            SHAPE_DEFS.pop(shape_used, None)

    def field(ai, li, key, ck=None):
        v = out[ai, li][key]
        return v.get(ck, 0.0) if ck is not None else v

    def extra(key, ck=None):
        if len(accums) == 1:
            f1, f2 = field(0, 0, key, ck), field(0, 1, key, ck)
            return (f1 + (f2 - f1) / (u2 - u1) * (uf - u1)) * seq_scale
        # bilinear: f(L, a) = a·(A·L + B) + (C·L + D)
        f11, f12 = field(0, 0, key, ck), field(0, 1, key, ck)  # a=1
        f21, f22 = field(1, 0, key, ck), field(1, 1, key, ck)  # a=2
        dL = u2 - u1
        A = (f22 - f21 - f12 + f11) / dL          # per-layer per-accum
        B = (f21 - f11) - A * u1                  # per-accum base
        C = (f12 - f11) / dL - A                  # per-layer const
        D = f11 - A * u1 - B - C * u1
        val = af * (A * uf + B) + (C * uf + D)
        return max(0.0, val) * seq_scale

    kinds = set()
    for v in out.values():
        kinds |= set(v["coll"])
    return {
        "flops_per_device": extra("flops"),
        "bytes_per_device": extra("bytes"),
        "collective_bytes_per_device": {k: extra("coll", k)
                                        for k in kinds},
        "units": [u1, u2, uf],
        "accum_eval": af,
        "seq_scale": seq_scale,
    }


def _lower_with_cfg(cfg, arch, shape, mesh, *, extra_rules=None,
                    accum=None, shard_residual=None, serve_fsdp=None):
    """lower_cell with an explicit (possibly calibration) config. The
    accumulation loop is unrolled so its per-microbatch collective traffic
    is counted exactly."""
    sd = SHAPE_DEFS[shape]
    kind = sd["kind"]
    if cfg.is_encoder and shape == "prefill_32k":
        kind = "prefill_encoder"
    if shard_residual is None:
        shard_residual = kind == "train" and cfg.d_model >= 2048
    rules = activation_rules(mesh, shard_residual=shard_residual)
    if extra_rules:
        rules.update(extra_rules)
    fsdp = ("pod", "data") if arch in TRAIN_LOWMEM else ("data",)
    if serve_fsdp is not None and kind != "train":
        fsdp = serve_fsdp
    n_accum = accum if accum is not None else TRAIN_ACCUM.get(arch, 1)
    with use_rules(mesh, rules):
        if kind == "train":
            opt = _make_opt(arch)
            accum_dtype = (jnp.bfloat16 if arch in TRAIN_LOWMEM
                           else jnp.float32)
            step = make_train_step(cfg, opt, accum_steps=n_accum,
                                   accum_dtype=accum_dtype,
                                   accum_unroll=True)
            state_abs, state_sh = state_specs(cfg, mesh, optimizer=opt,
                                              fsdp_axes=fsdp)
            batch = input_specs(cfg, shape, mesh)
            return jax.jit(step, in_shardings=(state_sh, None),
                           donate_argnums=(0,)).lower(state_abs,
                                                      batch), kind
        if kind in ("prefill", "prefill_encoder") or cfg.is_encoder:
            params_abs, params_sh = params_specs_only(cfg, mesh, fsdp)
            batch = input_specs(cfg, shape, mesh)
            if cfg.is_encoder:
                def enc(params, b):
                    logits, _, _ = forward(params, cfg, b, mode="train")
                    return logits
                return jax.jit(enc, in_shardings=(params_sh, None)
                               ).lower(params_abs, batch), kind
            caches_abs, caches_sh = cache_specs(cfg, shape, mesh)
            step = _step_fn(cfg, "prefill")
            return jax.jit(
                step, in_shardings=(params_sh, None, caches_sh),
                donate_argnums=(2,)).lower(params_abs, batch,
                                           caches_abs), kind
        params_abs, params_sh = params_specs_only(cfg, mesh, fsdp)
        batch = input_specs(cfg, shape, mesh)
        caches_abs, caches_sh = cache_specs(cfg, shape, mesh)
        step = _step_fn(cfg, "decode")
        return jax.jit(
            step, in_shardings=(params_sh, None, caches_sh, None),
            donate_argnums=(2,)).lower(
                params_abs, batch, caches_abs,
                jax.ShapeDtypeStruct((), jnp.int32)), kind


def save_rec(rec: dict):
    d = os.path.join(ART_DIR, rec["mesh"])
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{rec['arch']}__{rec['shape']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ensure_virtual_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="add exact (unrolled, extrapolated) roofline "
                         "quantities to each artifact")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the adopted §Perf layout changes "
                         "(replicated-params serving; no-ZeRO-R+accum8 "
                         "for internlm2; attn_qchunk for minicpm3)")
    args = ap.parse_args()

    cells = runnable_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if not cells:
        raise SystemExit("no matching cells")

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    failures = []
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            tag = f"{mesh_name} {arch} {shape}"
            out = os.path.join(ART_DIR, mesh_name,
                               f"{arch}__{shape}.json")
            if args.skip_existing and os.path.exists(out):
                done = json.load(open(out))
                if not args.calibrate or "calibrated" in done:
                    print(f"[skip] {tag}")
                    continue
            try:
                knobs = perf_knobs(arch, shape) if args.optimized else {}
                rec = run_cell(arch, shape, mesh_name, mesh,
                               calibrate=args.calibrate, **knobs)
                path = save_rec(rec)
                mem_gb = rec["memory"].get("temp_size_in_bytes", 0) / 2**30
                arg_gb = rec["memory"].get("argument_size_in_bytes",
                                           0) / 2**30
                print(f"[ok] {tag}: compile={rec['compile_s']}s "
                      f"args={arg_gb:.2f}GiB temp={mem_gb:.2f}GiB "
                      f"flops/dev={rec['flops_per_device']:.3g} -> {path}")
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"[FAIL] {tag}: {e!r}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
