#!/usr/bin/env python3
"""Compile a cell's full-width fused step for a described TPU v5e, with
no chip attached, and print its memory analysis.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py <cell> [--pages N] [--lanes N]

It lowers the program's token step (`make_token_step`, paged pool,
Pallas kernels, chunked prefill) from shapes alone at the configuration's
lanes, lane capacity and pages, and compiles it for one chip of a
described v5e:2x2.  What the compiler refuses there it would refuse on
the chip; the memory analysis sizes the pool.  Nothing runs, so it
gives no time.  The kernels' own CPU check (`ops.on_cpu`) is steered
here, in the tool, so that Mosaic and not the interpreter is lowered.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--pages", type=int)
    ap.add_argument("--lanes", type=int)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run
    from repro.kernels import ops
    from repro.models import model as M
    from repro.models.attention import PagedKV, PrefillChunk
    from repro.models.param import abstract
    from repro.serving.engine import make_token_step

    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_cpu = lambda: False
    spec = run.cell_spec(run.load_benchmark(), args.cell)
    config = dict(spec["config"])
    if args.lanes:
        config["lanes"] = args.lanes
    if args.pages:
        config["n_pages"] = args.pages
    cfg = run.program_config(config)
    bank, _, _ = run.make_bank(spec["traffic"], len(cfg.segments))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=chip)

    b, ps, c = config["lanes"], config["page_size"], config["prefill_chunk"]
    maxp = -(-config["lane_capacity"] // ps)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), abstract(
        M.model_defs(cfg), jnp.dtype(config["weight_dtype"])))
    caches = jax.tree.map(
        lambda sd: sds(*sd),
        M.paged_cache_specs(cfg, b, config["n_pages"], ps),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    states = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: tuple(s.init(b) for s in bank)))
    i32 = lambda *sh: sds(sh, jnp.int32)  # noqa: E731
    kv = PagedKV(i32(b, maxp), i32(b), i32(b))
    chunk = PrefillChunk(i32(b, c), i32(b, c), i32(b, c), i32(b, c), i32(b),
                         i32(b), sds((b,), bool), sds((b,), bool))
    step = make_token_step(params, cfg, bank, jit=True, donate=False,
                           carry_state=True, paged=True, paged_kernel=True,
                           prefill_slots=c)
    lowered = step.func.lower(params, i32(b), caches, i32(b),
                              sds((b,), bool), i32(b), kv, states, chunk)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    gb = 1e9
    arg, out, tmp = (ma.argument_size_in_bytes, ma.output_size_in_bytes,
                     ma.temp_size_in_bytes)
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    print(f"{args.cell}: lanes {b}, pages {config['n_pages']} of {ps}; "
          f"pool {pool / gb:.3f} GB; arguments {arg / gb:.3f} GB, outputs "
          f"{out / gb:.3f} GB, temporaries {tmp / gb:.3f} GB; "
          f"sum {(arg + out + tmp) / gb:.3f} GB; kernels "
          f"{compiled.as_text().count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
