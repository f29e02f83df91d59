"""deepseek-v2-lite-ep8 at its CPU-test size served through the normal
path -- `EngineStepper(kv="paged", prefill_chunk=...)`, chunked prefill
into the latent paged pool, then decode -- against the plain reference
(`bench/references/deepseek_v2.py`): YaRN MLA, a dense first segment,
8 held of 16 routed experts (top-3, gates not renormalised) with a
shared expert, and a node after every segment.  And the scheduler's
gate: MLA chunks, ssm/hybrid do not."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import strategy
from repro.configs import get_config
from repro.models import model as M
from repro.models.param import abstract, materialize
from repro.serving import runtime as rt
from repro.serving.runtime.request import Request
from repro.strategy.line import FixedNodeStrategy

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the reference's sizes of get_config("deepseek-v2-lite-ep8", smoke=True)
SIZES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 24, "d_ff": 96, "vocab": 256, "act": "swiglu",
         "n_segments": 2, "rope_theta": 10000.0, "norm_eps": 1e-6,
         "tie_embeddings": False, "first_dense": 1, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": 16, "experts_held": 8, "experts_first": 0,
         "top_k": 3, "moe_d_ff": 32, "shared_d_ff": 48,
         "norm_topk_prob": False, "routed_scaling_factor": 1.0,
         "yarn": {"factor": 40.0, "original_max_position": 4096,
                  "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
                  "mscale_all_dim": 0.707}}


def _reference():
    path = os.path.join(REPO, "bench", "references", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chunked_serve_matches_the_reference_at_every_readout():
    """Two lanes: a 21-token prompt over three 8-token chunks then decode
    at full depth, and a 13-token prompt whose decoded tokens exit at the
    ramp after the dense layer (its deeper latent rows never written).
    Every readout -- each first token from the chunk sweep's head, each
    decoded token's served logits -- matches the reference's full
    forward with the same exits."""
    cfg = get_config("deepseek-v2-lite-ep8", smoke=True)
    ref = _reference()
    seed = 2**31 + 17
    params = ref.program_params(SIZES, seed, jnp.float32)
    want = abstract(M.model_defs(cfg), jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(want)))
    n_nodes = len(cfg.segments)
    bank = (FixedNodeStrategy(n_nodes, n_nodes - 1),
            FixedNodeStrategy(n_nodes, 0))
    stepper = rt.EngineStepper(
        params, cfg, bank, n_lanes=2, cache_len=64, prompt_len=8,
        kv="paged", page_size=4, paged_kernel=True, prefill_chunk=8,
        prefill_budget=12, walk_io=True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (21, 13)]
    budget = 6
    for lane, p in enumerate(prompts):
        req = Request(rid=lane, prompt=p, max_tokens=budget)
        assert stepper.reserve(req)
        stepper.admit(lane, req)
    sid = np.arange(2, dtype=np.int32)
    occ = np.ones(2, bool)
    toks = [[], []]
    served = [[], []]
    for _ in range(40):
        prefilling = set(stepper._prefilling)
        tok, nodes, _, _, emit, (_, best) = stepper.step(occ, sid)
        best = np.asarray(best)
        for lane in range(2):
            if lane in prefilling and lane not in stepper._prefilling:
                toks[lane].append(int(tok[lane]))     # first token
                served[lane].append((n_nodes - 1, None))
            elif emit[lane] and len(toks[lane]) < budget + 1:
                toks[lane].append(int(tok[lane]))
                served[lane].append((int(nodes[lane]), best[lane]))
        if all(len(t) == budget + 1 for t in toks):
            break
    assert all(len(t) == budget + 1 for t in toks)
    assert [n for n, _ in served[1][1:]] == [0] * budget

    plain = ref.plain_params(SIZES, seed, jnp.float32)
    for lane, p in enumerate(prompts):
        lp, out = len(p), toks[lane]
        tokens = np.concatenate([p, out[:-1]]).astype(np.int32)
        depth = np.full(len(tokens), n_nodes - 1, np.int32)
        depth[lp:] = [n for n, _ in served[lane][1:]]
        read_pos = np.arange(lp - 1, lp - 1 + len(out), dtype=np.int32)
        read_node = np.asarray([n for n, _ in served[lane]], np.int32)
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(
                plain, SIZES, jnp.asarray(tokens), jnp.asarray(depth),
                jnp.asarray(read_pos), jnp.asarray(read_node),
                kv_dtype=jnp.bfloat16))
        # the first token is the head's argmax over the chunked prompt
        assert out[0] == int(lg[0].argmax()), lane
        for j in range(1, len(out)):
            # float32 against float32 at the highest precision, the same
            # bfloat16 latent cache: what differs is the order of sums
            # (absorbed against expanded attention, grouped against
            # dense experts), ~1e-6 on logits of order 1; a wrong
            # position, mask, rotary or expert moves them by ~0.1-1
            np.testing.assert_allclose(served[lane][j][1], lg[j],
                                       atol=1e-4, rtol=1e-4)
            assert out[j] == int(lg[j].argmax()), (lane, j)


def test_scheduler_chunks_latent_attention_but_not_state_space():
    """MLA segments take chunked prefill; ssm and hybrid mixers are
    still refused (their state is sequential over the prompt)."""
    def stepper(arch):
        cfg = get_config(arch, smoke=True)
        params = materialize(M.model_defs(cfg), jax.random.PRNGKey(0))
        casc = strategy.Cascade.uniform(len(cfg.segments), lam=0.5)
        bank = (strategy.make("always_last", casc),)
        return rt.EngineStepper(params, cfg, bank, n_lanes=1,
                                cache_len=16, prompt_len=4, kv="paged",
                                page_size=4, prefill_chunk=4)

    assert stepper("deepseek-v2-lite-ep8").prefill_chunk == 4
    assert stepper("deepseek-v2-lite-16b").prefill_chunk == 4
    for arch in ("mamba2-130m", "hymba-1.5b"):
        with pytest.raises(ValueError, match="attention segments only"):
            stepper(arch)
