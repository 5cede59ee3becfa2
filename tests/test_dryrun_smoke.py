"""Dry-run machinery smoke test.

Runs in a SUBPROCESS with a small forced host-device count so the main test
session keeps its single real CPU device (the assignment forbids setting the
512-device flag globally).  Exercises: mesh construction, logical shardings,
lower+compile of a reduced train step and decode step, and the HLO analyzer.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses, functools
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed.sharding import base_rules, logical_sharding, use_rules
    from repro.launch import hlo_analysis
    from repro.models import init_params, init_cache, param_axes, cache_axes
    from repro.serve.decode import serve_step
    from repro.train import make_optimizer, make_train_step, init_train_state
    from repro.train.optimizer import opt_state_axes
    from repro.train.trainer import TrainState

    cfg = get_config("llama3.2-1b").reduced()
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = base_rules()

    ocfg, opt = make_optimizer("adamw")
    step = make_train_step(cfg, opt, microbatches=2)
    params_s = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                              jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(opt.init, params_s)
    state_s = TrainState(params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32))
    p_ax = param_axes(cfg)
    o_ax = opt_state_axes(ocfg, params_s, p_ax)
    with mesh, use_rules(rules):
        p_sh = logical_sharding(mesh, rules, p_ax, params_s)
        o_sh = logical_sharding(mesh, rules, o_ax, opt_s)
        st_sh = TrainState(p_sh, o_sh, NamedSharding(mesh, P()))
        batch = {
            "inputs": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32),
        }
        b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        compiled = jax.jit(step, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, NamedSharding(mesh, P()))
                           ).lower(state_s, batch).compile()
    res = hlo_analysis.analyze(compiled.as_text(), mesh.size)
    ma = compiled.memory_analysis()

    # decode step too
    cache_s = jax.eval_shape(functools.partial(init_cache, cfg, 8, 32))
    c_ax = cache_axes(cfg, 8, 32)
    with mesh, use_rules(rules):
        c_sh = logical_sharding(mesh, rules, c_ax, cache_s)
        dec = jax.jit(lambda p, c, t, pos: serve_step(cfg, p, c, t, pos),
                      in_shardings=(p_sh, c_sh,
                                    NamedSharding(mesh, P("data", None)),
                                    NamedSharding(mesh, P())))
        dc = dec.lower(params_s, cache_s,
                       jax.ShapeDtypeStruct((8, 1), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32)).compile()

    print(json.dumps({
        "flops": res["flops"],
        "collective_bytes": res["collective_bytes"],
        "n_computations": res["n_computations"],
        "temp_bytes": ma.temp_size_in_bytes,
        "decode_ok": True,
    }))
""")


def test_dryrun_pipeline_in_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["decode_ok"]
    assert out["flops"] > 0
    assert out["collective_bytes"] > 0       # FSDP gathers must appear
    assert out["n_computations"] > 10
