"""Sharding rules: resolution logic + full coverage of every arch's param
tree + an 8-device SPMD integration test (subprocess, forced host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.models import model as model_lib
from repro.parallel import sharding as sh


class FakeMesh:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 4, "model": 4}
    size = 32


def test_resolve_divisible_and_drop():
    spec = sh._resolve((("pod", "data"), "model", None), (8, 12, 5),
                       FakeMesh(), uneven_ok=False)
    assert spec == jax.sharding.PartitionSpec(("pod", "data"), "model", None)
    # non-divisible dims are dropped when uneven is not allowed
    spec = sh._resolve((("pod", "data"), "model", None), (7, 5, 5),
                       FakeMesh(), uneven_ok=False)
    assert spec == jax.sharding.PartitionSpec(None, None, None)
    # uneven allowed: keep if dim >= axis/2
    spec = sh._resolve((None, "model"), (3, 10), FakeMesh(), uneven_ok=True)
    assert spec == jax.sharding.PartitionSpec(None, "model")
    spec = sh._resolve((None, "model"), (3, 1), FakeMesh(), uneven_ok=True)
    assert spec == jax.sharding.PartitionSpec(None, None)


@pytest.mark.parametrize("arch", sorted(ARCH_IDS), ids=str)
def test_param_rules_cover_every_arch(arch):
    cfg = get_smoke(arch)
    model = model_lib.get_model(cfg)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    name_of = sh.make_param_rule(expert_parallel=False)
    rules = sh.ShardingRules.default().rules
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        rule, leading = name_of(path)
        assert rule in rules, (arch, path)
        template = rules[rule]
        assert len(leaf.shape) - leading <= len(template), (arch, path)


@pytest.mark.parametrize("arch", sorted(ARCH_IDS), ids=str)
def test_cache_rules_cover_every_arch(arch):
    cfg = get_smoke(arch)
    model = model_lib.get_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    for path, _ in jax.tree_util.tree_flatten_with_path(cache)[0]:
        rule, _ = sh.cache_rule(path)
        assert rule is not None, (arch, path)


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke
    from repro.data import make_batch
    from repro.models import model as model_lib
    from repro.optim import AdamW
    from repro.parallel import sharding as sh

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = get_smoke("qwen2.5-14b")
    model = model_lib.get_model(cfg)
    shard = sh.make_shard_fn(mesh)
    opt = AdamW(lr=1e-3)
    step = model_lib.make_train_step(cfg, opt, shard, accum=2)

    params = model.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    p_specs = sh.state_specs(jax.eval_shape(lambda: params), mesh, "param")
    o_specs = sh.state_specs(jax.eval_shape(lambda: state), mesh, "opt")
    params = jax.device_put(params, p_specs)
    state = jax.device_put(state, o_specs)

    b = make_batch(cfg, 8, 32, 0, accum=2)
    b = {k: jnp.asarray(v) for k, v in b.items()}
    jstep = jax.jit(step, in_shardings=(p_specs, o_specs,
                                        jax.tree.map(lambda _: None, b)))
    params, state, m = jstep(params, state, b)
    sharded_loss = float(m["loss"])

    # reference: unsharded single-device run of the same step
    params0 = model.init_params(jax.random.PRNGKey(0))
    state0 = opt.init(params0)
    step0 = model_lib.make_train_step(cfg, opt, accum=2)
    _, _, m0 = jax.jit(step0)(params0, state0, b)
    print(json.dumps({"sharded": sharded_loss, "ref": float(m0["loss"])}))
""")


def test_spmd_train_step_matches_unsharded():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sharded"] == pytest.approx(res["ref"], rel=2e-2), res
