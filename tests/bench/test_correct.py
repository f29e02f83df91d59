"""The correctness check fails what it should: the lower-precision
control, and the faults a serving cell can have, each planted under a
run of the tests' tiny cell on the CPU (the look for a chip skipped).

  * a token altered where the step produces it;
  * a step that returns its state unchanged (the KV pool never written);
  * the exit decision moved (the strategy fold serves other nodes);
  * the page pool's accounting broken (a page leaked on each release).
"""

import pytest

from bench import run


def _run(root, seed=11):
    return run.run_cell("tiny.chat", seed, 1.0, False, root=root,
                        allow_cpu=True)


def _failed(out):
    return [k for k, c in out["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]


@pytest.fixture
def step_wrapper(monkeypatch):
    """Wrap the fused step the stepper builds with ``fn(out, args)``."""
    from repro.serving.runtime import scheduler

    def install(fn):
        real = scheduler.make_token_step

        def make(*a, **k):
            step = real(*a, **k)
            return lambda *args: fn(step(*args), args)
        monkeypatch.setattr(scheduler, "make_token_step", make)
    return install


def test_sound_run_is_correct(tiny_root, jax_cache_restored):
    out = _run(tiny_root)
    assert out["correct"] is True, out["checks"]


def test_altered_token_is_not_correct(tiny_root, jax_cache_restored,
                                      step_wrapper):
    vocab = 512

    def alter(out, args):
        tok = out[0]
        return (tok.at[0].set((tok[0] + 1) % vocab),) + tuple(out[1:])
    step_wrapper(alter)
    out = _run(tiny_root)
    assert out["correct"] is False and "logit_gap" in _failed(out)


def test_unchanged_state_is_not_correct(tiny_root, jax_cache_restored,
                                        step_wrapper):
    step_wrapper(lambda out, args: (out[0], args[1]) + tuple(out[2:]))
    out = _run(tiny_root)
    assert out["correct"] is False and "logit_gap" in _failed(out)


def test_moved_exit_decision_is_not_correct(tiny_root, jax_cache_restored,
                                            monkeypatch):
    real = run.make_bank

    def make_bank(traffic, n_nodes, root):
        # every lane goes on to the head: the profile says otherwise
        exits = dict(traffic["exits"])
        exits["continue"] = [1.0] * len(traffic["exits"]["continue"])
        bank, sid_of, _ = real(dict(traffic, exits=exits), n_nodes, root)
        return bank, sid_of, real(traffic, n_nodes, root)[2]
    monkeypatch.setattr(run, "make_bank", make_bank)
    out = _run(tiny_root)
    assert out["correct"] is False
    assert "served_node_mismatches" in _failed(out)


def test_leaked_page_is_not_correct(tiny_root, jax_cache_restored,
                                    monkeypatch):
    from repro.serving.kvpool import pool as pool_mod
    real = pool_mod.KVPool.release

    def release(self, lane):
        if self.n_held[lane]:       # keep a reference nobody holds
            self.allocator.incref(int(self.table[lane, 0]))
        real(self, lane)
    monkeypatch.setattr(pool_mod.KVPool, "release", release)
    out = _run(tiny_root)
    assert out["correct"] is False and _failed(out) == ["pool_violations"]


def test_control_is_not_correct(tiny_root, jax_cache_restored):
    """The reference one precision below the configuration's (float8 for
    bfloat16 weights), put in the program's place on the same prompts
    and tokens, fails the limit the program meets."""
    from bench.tools import control
    spec = run.cell_spec(run.load_benchmark(tiny_root), "tiny.chat",
                         tiny_root)
    limit = spec["limits"]["logit_gap"]["limit"]
    for r in control.readings(spec, [21, 22, 23], 1.0, tiny_root):
        assert r["control"] == "float8"
        assert r["program_gap"] <= limit < r["control_gap"], r
