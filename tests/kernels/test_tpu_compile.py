"""Compile checks for the TPU, made without one.

The TPU compiler is installed even where no chip is attached, so the
paged kernels are compiled here for a described v5e at paper-ee-100m
widths: what Mosaic refuses (block shapes off the (8, 128) tiling, too
much VMEM) fails here and not on the chip.  The last test lowers the
full-width serve step on the CPU from shapes alone and checks that the
weights enter it as arguments, not as constants baked into the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import strategy
from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as M
from repro.models.attention import PagedKV, PrefillChunk
from repro.models.param import abstract
from repro.serving.engine import make_token_step

# paper-ee-100m attention: 12 query heads over 12 KV heads, head_dim 64
# (ops pads it to 128 and the query group of 1 to 8); the serve shape of
# chip_smoke.py: 16 lanes, 1024-token lane capacity, 128-token chunks
LANES, HEADS, KV_HEADS, HEAD_DIM = 16, 12, 12, 64
CACHE_LEN, CHUNK = 1024, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _pool_shapes(one_chip, ps, pool_dtype):
    maxp = CACHE_LEN // ps
    n_pages = LANES * maxp + 1

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv = s((n_pages, ps, KV_HEADS, HEAD_DIM), pool_dtype)
    return s, kv, s((n_pages, ps), jnp.int32), s((LANES, maxp), jnp.int32)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("ps", [16, 128])
@pytest.mark.parametrize("kernel", ["paged_attention", "paged_prefill"])
def test_paged_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                       kernel, ps, pool_dtype):
    s, kv, pos_pages, table = _pool_shapes(one_chip, ps, pool_dtype)
    bf = jnp.bfloat16
    if kernel == "paged_attention":
        def f(q, k, v, pp, t, qp):
            return ops.paged_attention(q, k, v, pp, t, qp, scale=0.125,
                                       interpret=False)
        args = (s((LANES, HEADS, HEAD_DIM), bf), kv, kv, pos_pages, table,
                s((LANES,), jnp.int32))
    else:
        def f(q, k, v, pp, t, qp, st, ck, cv):
            return ops.paged_prefill(q, k, v, pp, t, qp, st, ck, cv, qp,
                                     scale=0.125, interpret=False)
        args = (s((LANES, CHUNK, HEADS, HEAD_DIM), bf), kv, kv, pos_pages,
                table, s((LANES, CHUNK), jnp.int32), s((LANES,), jnp.int32),
                s((LANES, CHUNK, KV_HEADS, HEAD_DIM), bf),
                s((LANES, CHUNK, KV_HEADS, HEAD_DIM), bf))
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _constant_sizes(module) -> list[int]:
    """Element counts of every constant in a lowered StableHLO module."""
    from jax._src.lib.mlir import ir
    sizes = []

    def visit(op):
        if op.name == "stablehlo.constant":
            shape = ir.RankedTensorType(op.results[0].type).shape
            sizes.append(int(np.prod(shape, dtype=np.int64)))
        return ir.WalkResult.ADVANCE

    module.operation.walk(visit)
    return sizes


def test_full_width_step_takes_weights_as_arguments():
    """The full-width paged + chunked serve step, lowered from
    `jax.eval_shape`-style params: no constant may be weight-sized (the
    smallest weight, one 768x768 projection, has 589,824 elements)."""
    cfg = get_config("paper-ee-100m", smoke=False)
    params = abstract(M.model_defs(cfg), jnp.float32)
    casc = strategy.Cascade.uniform(cfg.n_ramps + 1, lam=0.5)
    bank = (strategy.make("always_last", casc),)
    ps, maxp = 128, CACHE_LEN // 128
    step = make_token_step(params, cfg, bank, donate=False,
                           carry_state=True, paged=True,
                           prefill_slots=CHUNK)
    i32 = jnp.int32

    def s(shape, dt=i32):
        return jax.ShapeDtypeStruct(shape, dt)

    caches = jax.tree.map(
        lambda spec: s(*spec),
        M.paged_cache_specs(cfg, LANES, LANES * maxp + 1, ps),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    states = tuple(jax.eval_shape(lambda st=st: st.init(LANES))
                   for st in bank)
    kv = PagedKV(page_table=s((LANES, maxp)), write_page=s((LANES,)),
                 write_slot=s((LANES,)))
    rows = s((LANES, CHUNK))
    chunk = PrefillChunk(tok=rows, pos=rows, dest_page=rows, dest_slot=rows,
                         start=s((LANES,)), last_idx=s((LANES,)),
                         emit=s((LANES,), bool), active=s((LANES,), bool))
    lowered = step.func.lower(*step.args, s((LANES,)), caches, s((LANES,)),
                              s((LANES,), bool), s((LANES,)), kv, states,
                              chunk)
    sizes = _constant_sizes(lowered.compiler_ir("stablehlo"))
    assert sizes, "walk found no constants at all: the check is blind"
    assert max(sizes) < 2 ** 18, sorted(sizes)[-5:]
