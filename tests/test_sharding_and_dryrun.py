"""Sharding specs for all archs + a subprocess dry-run on a tiny virtual
mesh (keeps the main test process at 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import registry
from repro.models.config import SHAPES


def test_param_shardings_cover_all_archs():
    """Specs build for every arch on a (2,2) host-style mesh shape without
    touching devices (uses the real 1-CPU mesh)."""
    from repro.launch import specs as SP
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for name, cfg in registry().items():
        sh = SP.param_shardings(cfg, mesh)
        leaves = jax.tree.leaves(sh)
        assert leaves, name


def test_input_specs_shapes():
    from repro.launch import specs as SP
    for name, cfg in registry().items():
        for sname, shape in SHAPES.items():
            specs = SP.input_specs(cfg, shape)
            if shape.kind == "train":
                assert specs["batch"]["targets"].shape == (
                    shape.global_batch, shape.seq_len)
            elif shape.kind == "prefill":
                assert specs["inputs"].shape[0] == shape.global_batch
            else:
                assert specs["tokens"].shape == (shape.global_batch, 1)
                cl = SP.cache_len_for(cfg, shape)
                if shape.kind == "long_decode":
                    assert cl <= cfg.sliding_window or cfg.attn_free


def test_cache_len_long_decode_is_sub_quadratic():
    from repro.launch import specs as SP
    long = SHAPES["long_500k"]
    for name, cfg in registry().items():
        cl = SP.cache_len_for(cfg, long)
        assert cl < long.seq_len, f"{name}: long_500k must not keep 512k KV"


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, functools, json
    import jax, jax.numpy as jnp
    from repro import sharding as SH
    from repro.configs import registry
    from repro.launch import specs as SP
    from repro.train import steps as TS
    from repro.models import transformer as T

    cfg = dataclasses.replace(registry()["{arch}"].reduced(), dtype="float32")
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh, SH.axis_env(mesh, batch=("data",)):
        st_sh = SP.state_shardings(cfg, mesh)
        state = jax.eval_shape(lambda: TS.init_state(cfg, jax.random.PRNGKey(0)))
        batch = {{"inputs": jax.ShapeDtypeStruct((4, 16), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((4, 16), jnp.int32)}}
        from jax.sharding import NamedSharding, PartitionSpec as P
        b_sh = {{k: NamedSharding(mesh, P("data", None)) for k in batch}}
        fn = functools.partial(TS.train_step, cfg, TS.opt_config_for(cfg))
        jitted = jax.jit(fn, donate_argnums=(0,),
                         in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
        compiled = jitted.lower(state, batch).compile()
        ca = compiled.cost_analysis()
        print(json.dumps({{"ok": True, "flops": ca.get("flops", 0)}}))
""")


@pytest.mark.parametrize("arch", ["yi-6b", "olmoe-1b-7b", "rwkv6-1.6b",
                                  "hymba-1.5b", "minicpm3-4b"])
def test_subprocess_tiny_mesh_train_lowers(arch):
    """Real SPMD compile of a reduced config on an 8-device virtual mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the forced 8-device host platform only exists on the CPU backend
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SUBPROC.format(arch=arch)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
