"""Sharding machinery: spec_for divisibility gating, input_specs shapes,
hlo_cost parser invariants, and an 8-fake-device lower+compile smoke of a
reduced config (subprocess — jax locks device count at first init)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


class _FakeMesh:
    """spec_for only consults mesh.shape."""

    def __init__(self, **shape):
        self.shape = shape


def test_spec_for_divisibility_gating():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.rules import BASELINE_RULES, spec_for
    mesh = _FakeMesh(data=4, model=8)
    s = spec_for(mesh, BASELINE_RULES, (64, 128), ("embed", "mlp"))
    assert s == P(None, "model")
    # 63 is not divisible by model=8 -> replicate
    s = spec_for(mesh, BASELINE_RULES, (63,), ("mlp",))
    assert s == P()
    # batch gets both pod+data when present and divisible
    mesh2 = _FakeMesh(pod=2, data=4, model=8)
    s = spec_for(mesh2, BASELINE_RULES, (16, 128), ("batch", None))
    assert s == P(("pod", "data"))
    # batch=4 not divisible by pod*data=8 -> replicate
    s = spec_for(mesh2, BASELINE_RULES, (4,), ("batch",))
    assert s == P()
    # an axis is never used twice in one spec
    s = spec_for(mesh, BASELINE_RULES, (64, 64), ("mlp", "heads"))
    assert s == P("model", None) or s == P("model")


def test_input_specs_cover_all_modes():
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.shapes import SHAPES, input_specs, resolve_config
    from repro.sharding.rules import BASELINE_RULES
    mesh = make_local_mesh(1, 1)
    for arch in ("qwen3-4b", "musicgen-large", "phi-3-vision-4.2b"):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            c = resolve_config(cfg, shape)
            specs = input_specs(c, shape, mesh, BASELINE_RULES)
            assert specs, (arch, shape.name)
            for v in specs.values():
                assert all(d > 0 for d in v.shape)


def test_resolve_config_long_context():
    from repro.configs import get_config
    from repro.launch.shapes import SHAPES, resolve_config
    long = SHAPES["long_500k"]
    # full attention gets the sliding-window override
    c = resolve_config(get_config("qwen3-4b"), long)
    assert c.is_subquadratic
    # SSM passes through untouched
    c2 = resolve_config(get_config("mamba2-130m"), long)
    assert c2.name == "mamba2-130m"
    # starcoder2 has a native window already
    c3 = resolve_config(get_config("starcoder2-3b"), long)
    assert c3.is_subquadratic


def test_hlo_cost_counts_scan_trips():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_cost import analyze

    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    hlo = jax.jit(f).lower(x, ws).compile().as_text()
    r = analyze(hlo)
    expected = 12 * 2 * 128 ** 3
    assert abs(r.flops - expected) / expected < 0.01


DRYRUN_SMOKE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_config
    from repro.launch.shapes import ShapeSpec, input_specs
    from repro.models import model as M
    from repro.models.param import ParamDef
    from repro.sharding.ctx import activation_sharding
    from repro.sharding.rules import BASELINE_RULES, spec_for
    from repro.training.loop import make_train_step
    from repro.training.optimizer import AdamWConfig

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    cfg = get_config("{arch}", smoke=True)
    defs = M.model_defs(cfg)
    shape = ShapeSpec("t", 64, 8, "train")

    def ab(d, dt):
        return jax.ShapeDtypeStruct(d.shape, dt, sharding=NamedSharding(
            mesh, spec_for(mesh, BASELINE_RULES, d.shape, d.axes)))
    params = jax.tree.map(lambda d: ab(d, jnp.float32), defs,
                          is_leaf=lambda x: isinstance(x, ParamDef))
    opt = {{"mu": params, "nu": params,
           "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    batch = input_specs(cfg, shape, mesh, BASELINE_RULES)
    step = make_train_step(cfg, AdamWConfig(), num_microbatches=2)
    with mesh, activation_sharding(("data",)):
        compiled = jax.jit(step).lower(params, opt, batch).compile()
    print(json.dumps({{"ok": True,
                      "flops": compiled.cost_analysis()["flops"]}}))
""")


@pytest.mark.parametrize("arch", ["qwen3-4b", "phi3.5-moe-42b-a6.6b",
                                  "mamba2-130m"])
def test_train_step_lowers_on_8_fake_devices(arch):
    """Reduced-config train_step must lower+compile on a 2x4 mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "../../src")
    out = subprocess.run(
        [sys.executable, "-c", DRYRUN_SMOKE.format(arch=arch)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0
