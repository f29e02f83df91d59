"""Is what the timed path produced correct?

Run after the window has closed, `memory_peak_bytes` has been read and
the program's state is freed.  Each number compared is printed beside
its limit; the run is correct when every number is within its limit.

  * ``logit_gap``: a sample of the requests the window finished, drawn
    from the seed with the longest among them, is run through the plain
    reference (`bench/references/`), in float32 at the highest
    precision, with the weights drawn again from the seed.  For every
    served token -- the first, from the head on the final prefill chunk,
    and each decoded one, from the node it was served at -- the gap by
    which its logit lies below the reference's best at that position
    and node.  The number is the widest gap.  It covers chunked prefill
    into the pool, decode through the paged kernel in every segment,
    each readout, and the masking of what an early exit did not write.
  * ``served_node_mismatches``: tokens of all finished requests whose
    served node is not the one the exit policy decides for that request
    and token index (recomputed on the host).  Limit 0.
  * ``budget_mismatches``: finished requests that did not get exactly
    their budget of tokens, or got a token id outside the vocabulary.
    Limit 0.
  * ``pool_violations``: the page pool's invariant violations, and pages
    still in use once every lane is released and the prefix cache is
    dropped (as `chip_smoke._check_serve` checks).  Limit 0.
"""

from __future__ import annotations

import numpy as np

__all__ = ["sample", "pool_violations", "served_nodes", "budgets",
           "logit_gaps"]


def sample(logs, n: int, seed: int) -> list:
    """Up to ``n`` finished requests: the one with the most served
    tokens, and the rest drawn from the seed."""
    done = sorted((g for g in logs if g.done), key=lambda g: g.rid)
    if not done:
        return []
    longest = max(done, key=lambda g: (len(g.tokens), g.prompt_len))
    rest = [g for g in done if g is not longest]
    rng = np.random.default_rng([seed % (1 << 63), 7])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def pool_violations(pool) -> list[str]:
    bad = list(pool.check_invariants())
    for lane in np.flatnonzero(pool.n_held):
        pool.release(int(lane))
    pool.prefix.clear()
    if pool.pages_in_use:
        bad.append(f"{pool.pages_in_use} pages in use with no lane and "
                   "no prefix cache")
    return bad + list(pool.check_invariants())


def served_nodes(logs, expected_of) -> int:
    """Decoded tokens served at another node than the policy decides;
    the first token must come from the head."""
    bad = 0
    for g in logs:
        if not g.nodes:
            continue
        want = expected_of(g.rid, len(g.nodes) - 1)
        got = np.asarray(g.nodes[1:])
        bad += int((got != want).sum()) + int(g.nodes[0] != -1)
    return bad


def budgets(logs, vocab: int) -> int:
    bad = 0
    for g in logs:
        if g.done and (g.n_decoded != g.budget
                       or not all(0 <= t < vocab for t in g.tokens)):
            bad += 1
    return bad


def request_arrays(g, n_nodes: int, seq: int, rows: int):
    """The reference's inputs for one finished request: its input tokens
    by position, how deep each position ran, and where each served token
    is read (position, node) with its id."""
    p, n = g.prompt_len, g.n_decoded
    tokens = np.zeros(seq, np.int32)
    tokens[:p] = g.prompt
    tokens[p:p + n] = g.tokens[:n]           # t0 and all but the last
    depth = np.full(seq, -1, np.int32)
    depth[:p] = n_nodes - 1
    nodes = np.asarray(g.nodes[1:], np.int32)
    depth[p:p + n] = nodes
    read_pos = np.zeros(rows, np.int32)
    read_node = np.full(rows, -1, np.int32)
    served = np.zeros(rows, np.int32)
    read_pos[:n + 1] = np.arange(p - 1, p + n)
    read_node[0] = n_nodes - 1
    read_node[1:n + 1] = nodes
    served[:n + 1] = g.tokens
    return tokens, depth, read_pos, read_node, served


def logit_gaps(ref, plain, sizes, picked, *, seq: int, rows: int,
               kv_dtype, control: str | None = None):
    """Widest gap over the sample (and the control's, if asked)."""
    import jax
    import jax.numpy as jnp

    n_nodes = sizes["n_segments"]
    fn = jax.jit(lambda pl, *a: ref.check_logits(
        pl, sizes, *a, kv_dtype=kv_dtype, control=control))
    gaps, cgaps = [], []
    for g in picked:
        arrs = request_arrays(g, n_nodes, seq, rows)
        valid = arrs[3] >= 0
        gap, cgap = fn(plain, *map(jnp.asarray, arrs))
        gaps.append(np.asarray(gap)[valid])
        if cgap is not None:
            cgaps.append(np.asarray(cgap)[valid])
    gaps = np.concatenate(gaps)
    if control is None:
        return float(gaps.max()), None, len(gaps)
    cgaps = np.concatenate(cgaps)
    log_flips(gaps, cgaps)
    return float(gaps.max()), float(cgaps.max()), len(gaps)


def log_flips(gaps, cgaps) -> None:
    """Tokens off the reference's argmax, and the mean gap, of the
    program and of the control (information for setting limits)."""
    import sys
    print(f"flips: program {int((gaps > 0).sum())} (mean gap "
          f"{gaps.mean():.3e}), control {int((cgaps > 0).sum())} (mean gap "
          f"{cgaps.mean():.3e}) of {len(gaps)}", file=sys.stderr,
          flush=True)
