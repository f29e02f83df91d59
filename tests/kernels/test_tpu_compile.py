"""Compile checks for the TPU, made without one.

The TPU compiler is installed even where no chip is attached, so the
paged kernels are compiled here for a described v5e at paper-ee-100m
widths: what Mosaic refuses (block shapes off the (8, 128) tiling, too
much VMEM) fails here and not on the chip.  The full-width serve step
is compiled there too, donated, to check that the KV pool stays in
place (DESIGN.md §8); and lowered on the CPU from shapes alone, to
check that the weights enter it as arguments, not as constants baked
into the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
import re

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
# (ops pads the query group of 1 to 8, and not hd); the serve shape of
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

    kv = s((n_pages, KV_HEADS, HEAD_DIM, ps), pool_dtype)
    return (s, kv, s((n_pages, 1, ps), jnp.int32),
            s((LANES, maxp), jnp.int32))


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


def _largest(shape_text: str) -> tuple:
    """Dims of the largest array in an HLO result shape (a tuple for
    copy-start)."""
    shapes = [tuple(int(d) for d in dims.split(",") if d)
              for dims in re.findall(r"\w+\[([\d,]*)\]", shape_text)]
    return max(shapes, key=lambda sh: int(np.prod(sh, dtype=np.int64)),
               default=())


def _donated_step(one_chip, cfg, lanes, n_pages, ps, maxp):
    """The donated full-width paged + chunked serve step of ``cfg``
    lowered for the described chip from shapes alone; returns (lowered,
    params, caches)."""
    bf = jnp.bfloat16

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)

    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          abstract(M.model_defs(cfg), bf))
    casc = strategy.Cascade.uniform(len(cfg.segments), lam=0.5)
    bank = (strategy.make("always_last", casc),)
    caches = jax.tree.map(
        lambda spec: s(*spec),
        M.paged_cache_specs(cfg, lanes, n_pages, ps),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    states = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
        lambda: tuple(st.init(lanes) for st in bank)))
    rows = s((lanes, CHUNK))
    chunk = PrefillChunk(tok=rows, pos=rows, dest_page=rows, dest_slot=rows,
                         start=s((lanes,)), last_idx=s((lanes,)),
                         emit=s((lanes,), bool), active=s((lanes,), bool))
    kv = PagedKV(page_table=s((lanes, maxp)), write_page=s((lanes,)),
                 write_slot=s((lanes,)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_cpu", lambda: False)     # Mosaic, not interpret
        step = make_token_step(params, cfg, bank, donate=True,
                               carry_state=True, paged=True,
                               paged_kernel=True, prefill_slots=CHUNK)
        lowered = step.func.lower(
            params, s((lanes,)), caches, s((lanes,)), s((lanes,), bool),
            s((lanes,)), kv, states, chunk)
    return lowered, params, caches


def _aliased(text: str) -> int:
    aliases = re.search(r"input_output_alias=\{(.*?)\}\s*,?\s*entry_",
                        text)
    assert aliases, "no input_output_alias: the pool is not donated"
    return len(re.findall(r"may-alias|must-alias", aliases.group(1)))


def test_donated_full_width_step_keeps_the_pool_in_place(
        one_chip, no_persistent_cache):
    """The full-width paged + chunked step at paper-ee widths (32 lanes,
    257 pages of 128), donated and compiled for a described v5e: every
    pool leaf is aliased to its output, no copy, transpose, pad or
    dynamic-slice the size of one layer's K pool is left anywhere in
    the program, the arguments do not grow, and its temporaries stay
    below the pool itself.  A model without expert layers returns no
    counters: tokens, caches, served nodes, the two segment counts and
    the bank's states."""
    cfg = get_config("paper-ee-100m", smoke=False)
    lanes, n_pages, ps = 32, 257, 128
    lowered, params, caches = _donated_step(one_chip, cfg, lanes, n_pages,
                                            ps, CACHE_LEN // ps)
    assert len(lowered.out_info) == 6
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_cpu", lambda: False)
        compiled = lowered.compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    leaves = jax.tree.leaves(caches)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert pool_bytes == 1_214_257_152
    assert _aliased(text) == len(leaves)
    assert ma.alias_size_in_bytes == pool_bytes
    # the stored layout pads nothing: the arguments (weights, pool,
    # inputs) take no more than with the hd-minor pool before it
    assert ma.argument_size_in_bytes <= 1_461_456_896
    attn = cfg.segments[0].block.attn
    layer_pool = n_pages * ps * attn.n_kv_heads * attn.head_dim
    # a weight as large (the tied embedding, which the readouts read
    # whole) may be staged into faster memory; no pool array may move
    weights = {tuple(a.shape) for a in jax.tree.leaves(params)}
    moved = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) ([a-z][a-z\-]*)\(", line)
        if not m or m.group(2) not in ("copy", "copy-start", "transpose",
                                       "pad", "dynamic-slice"):
            continue
        dims = _largest(m.group(1))
        if np.prod(dims, dtype=np.int64) >= layer_pool \
                and dims not in weights:
            moved.append(line.strip()[:160])
    assert not moved, moved
    assert text.count("tpu_custom_call") >= 2
    assert ma.temp_size_in_bytes < pool_bytes, ma.temp_size_in_bytes


def test_latent_expert_step_fits_one_chip_with_the_pool_in_place(
        one_chip, no_persistent_cache):
    """deepseek-v2-lite-ep8's step at its cell's sizes (16 lanes of
    8,192 tokens, 1,025 pages of 128), donated and compiled for a
    described v5e: the latent pool (4.095 GB) aliased leaf by leaf,
    weights and pool within the chip's 15.75 GB with temporaries under
    a quarter of the pool, the held experts' grouped products as
    ragged-dot kernels, and the expert counters among the outputs."""
    cfg = get_config("deepseek-v2-lite-ep8", smoke=False)
    lanes, n_pages, ps = 16, 1025, 128
    lowered, params, caches = _donated_step(one_chip, cfg, lanes, n_pages,
                                            ps, 8192 // ps)
    assert len(lowered.out_info) == 7
    assert sorted(lowered.out_info[-1]) == ["expert_rows",
                                            "expert_rows_live",
                                            "expert_rows_max"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_cpu", lambda: False)
        compiled = lowered.compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    leaves = jax.tree.leaves(caches)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert pool_bytes == 4_095_014_400      # 1025 x 128 x 27 x 1156 B
    assert _aliased(text) == len(leaves)
    assert ma.alias_size_in_bytes == pool_bytes
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.75e9
    # 0.47 GB: the decode gathers of one layer's pages and the chunk
    # sweep's expert buffers; one more copy of the latent pool would not
    # fit under a quarter of it
    assert ma.temp_size_in_bytes < pool_bytes / 4
    assert "ragged-dot" in text


def test_stepper_programs_donate_every_pool_leaf(one_chip):
    """Off the CPU, `EngineStepper` builds every program that writes the
    pool — the fused step, the page ops (`_prep`), the admission reset
    (`_reset`) and the admit program — with the caches donated: lowered
    for the described v5e, each marks every pool leaf as donated."""
    from repro.serving import runtime as rt

    cfg = get_config("paper-ee-100m", smoke=True)
    params = abstract(M.model_defs(cfg), jnp.float32)
    casc = strategy.Cascade.uniform(cfg.n_ramps + 1, lam=0.5)
    bank = (strategy.make("always_last", casc),)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        stepper = rt.EngineStepper(params, cfg, bank, n_lanes=2,
                                   cache_len=32, prompt_len=8, kv="paged",
                                   page_size=8, paged_kernel=True,
                                   prefill_chunk=4)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    n, lp = stepper.n_lanes, stepper.pool.lane_pages
    i32 = np.zeros(n, np.int32)
    kv = PagedKV(np.zeros((n, lp), np.int32), i32, i32)
    chunk, _, _ = stepper._build_chunk({})
    prompt = np.zeros((1, 8), np.int32)
    caches = on_chip(stepper.caches)
    lowered = {
        "step": stepper._step.func.lower(*on_chip(
            (params, stepper.tok, stepper.caches, stepper.pos,
             np.ones(n, bool), i32, kv, stepper.states, chunk))),
        "prep": stepper._prep.lower(caches, *on_chip((i32, i32, i32))),
        "reset": stepper._reset.lower(caches, on_chip(i32)),
        "admit": stepper._admit.func.lower(*on_chip(
            (params, stepper.caches, stepper.tok, stepper.pos, prompt,
             np.int32(0), prompt[0], prompt[0], np.zeros(lp, np.int32)))),
    }
    where = {"step": 2, "prep": 0, "reset": 0, "admit": 1}
    for name, low in lowered.items():
        info = jax.tree.leaves(low.args_info[0][where[name]])
        assert len(info) == len(jax.tree.leaves(stepper.caches)), name
        assert all(a.donated for a in info), name


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
