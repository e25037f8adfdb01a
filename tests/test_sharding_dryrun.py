"""Distribution tests on a small host-device mesh: partition-spec rules,
logical sharding sanitization, and a reduced-scale lower+compile of the
dry-run machinery (the full 512-device run is `repro.launch.dryrun`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.sharding.logical import sanitize_spec, shard, use_rules
from repro.sharding.partition_specs import (activation_rules, data_specs,
                                            param_specs)

N_DEV = len(jax.devices())


def small_mesh():
    return make_debug_mesh(N_DEV)


def test_sanitize_spec_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("data",))
    s = sanitize_spec(P("data"), (7,), mesh)
    assert s == P(None) or s == P("data")  # size-1 axis always divides
    mesh2 = jax.make_mesh((1,), ("model",))
    del mesh2


def test_param_specs_cover_all_archs():
    mesh = small_mesh()
    for arch in ("smollm-360m", "mixtral-8x22b", "zamba2-2.7b",
                 "rwkv6-3b", "deepseek-v2-236b"):
        cfg = get_config(arch, reduced=True)
        from repro.models import init_model
        shapes = jax.eval_shape(
            lambda: init_model(cfg, jax.random.PRNGKey(0)))
        specs = param_specs(shapes, mesh)
        # every leaf got a spec of matching rank
        def check(s, l):
            assert len(s) == len(l.shape)
            for d, entry in enumerate(s):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert l.shape[d] % size == 0
        jax.tree.map(check, specs, shapes)


def test_shard_noop_outside_rules():
    x = jnp.ones((4, 4))
    assert shard(x, "act_btd") is x


def test_shard_applies_constraint_under_jit():
    mesh = small_mesh()
    rules = activation_rules(mesh)

    def f(x):
        return shard(x, "act_btf") * 2

    with use_rules(mesh, rules):
        lowered = jax.jit(f).lower(
            jax.ShapeDtypeStruct((4, 8, mesh.shape["model"] * 4),
                                 jnp.float32))
        txt = lowered.as_text()
    assert "sharding" in txt


@pytest.mark.parametrize("arch", ["smollm-360m", "mixtral-8x22b",
                                  "zamba2-2.7b", "rwkv6-3b"])
def test_reduced_dryrun_compiles(arch):
    """lower+compile a reduced config train step on the host mesh —
    the same machinery the 512-device dry-run uses."""
    from repro.train import adamw
    from repro.train.train_step import init_train_state, make_train_step
    cfg = get_config(arch, reduced=True)
    mesh = small_mesh()
    rules = activation_rules(mesh)
    opt = adamw()
    step = make_train_step(cfg, opt)
    with use_rules(mesh, rules):
        from repro.models import init_model
        shapes = jax.eval_shape(lambda: init_train_state(
            init_model(cfg, jax.random.PRNGKey(0)), opt))
        from repro.sharding.partition_specs import param_shardings
        from jax.sharding import NamedSharding
        sh = {
            "params": param_shardings(shapes["params"], mesh),
            "opt": {"m": param_shardings(shapes["opt"]["m"], mesh),
                    "v": param_shardings(shapes["opt"]["v"], mesh),
                    "count": NamedSharding(mesh, P())},
            "step": NamedSharding(mesh, P()),
        }
        state_abs = jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=h), shapes, sh)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        if cfg.frontend == "vision_stub":
            batch["patches"] = jax.ShapeDtypeStruct(
                (8, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
        compiled = jax.jit(step, in_shardings=(sh, None)).lower(
            state_abs, batch).compile()
    from repro.launch.dryrun import cost_analysis_dict
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ar = bf16[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag = f32[4,256]{1,0} all-gather(%y), dimensions={0}
  %rs = bf16[2,64]{1,0} reduce-scatter(%z), dimensions={0}
  %cp = f32[8]{0} collective-permute(%w)
  %other = f32[8]{0} add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 16 * 128 * 2
    assert out["all-gather"] == 4 * 256 * 4
    assert out["reduce-scatter"] == 2 * 64 * 2
    assert out["collective-permute"] == 32
    assert "add" not in out


def test_runnable_cells_skips_documented():
    from repro.configs import runnable_cells
    cells = runnable_cells()
    assert ("hubert-xlarge", "decode_32k") not in cells
    assert ("hubert-xlarge", "long_500k") not in cells
    assert ("gemma2-2b", "long_500k") not in cells
    assert ("zamba2-2.7b", "long_500k") in cells
    assert ("rwkv6-3b", "long_500k") in cells
    assert ("mixtral-8x22b", "long_500k") in cells
    assert len(cells) == 32


def test_dryrun_import_leaves_xla_flags_untouched():
    """Regression: importing launch/dryrun as a library must not mutate
    XLA_FLAGS (it used to force 512 host devices at import time, fighting
    benchmarks/common.py:force_host_devices). Topology selection belongs
    to the CLI entrypoint (ensure_virtual_devices) alone."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = (
        "import os\n"
        "os.environ.pop('XLA_FLAGS', None)\n"
        "import repro.launch.dryrun as d\n"
        "assert 'XLA_FLAGS' not in os.environ, os.environ.get('XLA_FLAGS')\n"
        "d.ensure_virtual_devices(4)\n"
        "assert os.environ['XLA_FLAGS'] == "
        "'--xla_force_host_platform_device_count=4'\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=2'\n"
        "d.ensure_virtual_devices(512)\n"   # explicit setting wins
        "assert os.environ['XLA_FLAGS'] == "
        "'--xla_force_host_platform_device_count=2'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_mcm_planner():
    from repro.sharding.mcm_planner import arch_to_task, plan, tpu_hw
    cfg = get_config("internlm2-20b")
    task = arch_to_task(cfg, 1024, 16, layers=2)
    assert len(task) > 4
    hw = tpu_hw((4, 4))
    assert hw.R == 128 and hw.mcm_type.value == "C"
    r = plan(cfg, (4, 4), 512, 16, layers=2, ga_budget=5)
    assert r.baseline_latency > 0
    assert r.optimized_latency <= r.baseline_latency * 1.001
    assert r.nonuniform_headroom >= 0.99
