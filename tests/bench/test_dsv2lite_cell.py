"""The dsv2lite.docqa cell through the harness unedited, at the CPU-test
size of its configuration: the same files, with the smoke model's sizes
and a mix of short prompts, served on the CPU (the look for a chip
skipped).  A traced run is correct against the deepseek_v2 reference
and reports the expert-layer and latent-attention metrics, and every
other per-layer metric the benchmark lists for the cell."""

import json
import os
import shutil

from bench import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "dsv2lite.docqa"
NEW_METRICS = ("expert_rows_per_live", "expert_load_max_per_mean",
               "latent_rows_read_per_live")
# get_config("deepseek-v2-lite-ep8", smoke=True) in the reference's terms
SMOKE_SIZES = {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
    "head_dim": 24, "d_ff": 96, "vocab": 256, "act": "swiglu",
    "n_segments": 2, "rope_theta": 10000.0, "norm_eps": 1e-6,
    "tie_embeddings": False, "first_dense": 1, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 16, "experts_held": 8, "experts_first": 0,
    "top_k": 3, "moe_d_ff": 32, "shared_d_ff": 48, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0,
    "yarn": {"factor": 40.0, "original_max_position": 4096,
             "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
             "mscale_all_dim": 0.707}}


def _small_root(tmp_path) -> str:
    """A checkout-like root: the benchmark's files and BENCHMARK.json
    with the cell alone; its configuration at the smoke size and its mix
    cut to prompts of 10-40 tokens."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    bench["workloads"] = [cell]
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] == cell["config"]]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    path = root / "bench" / "configs" / (cell["config"] + ".json")
    config = json.loads(path.read_text())
    config.update(smoke=True, sizes=SMOKE_SIZES, lanes=4,
                  lane_capacity=64, page_size=8, n_pages=33,
                  prefill_chunk=8, prefill_budget=16)
    path.write_text(json.dumps(config))
    path = root / "bench" / "traffic" / (cell["traffic"] + ".json")
    traffic = json.loads(path.read_text())
    traffic.update(rate=4.0, drain_s=60, sample=3,
                   prompt=dict(traffic["prompt"], median=20, min=10,
                               max=40),
                   output=dict(traffic["output"], median=5, min=2, max=8),
                   exits=dict(traffic["exits"], members=2,
                              **{"continue": [0.5]}))
    path.write_text(json.dumps(traffic))
    return str(root)


def test_traced_run_reports_the_expert_and_latent_metrics(
        tmp_path, jax_cache_restored):
    root = _small_root(tmp_path)
    out = run.run_cell(CELL, 2**31 + 41, 2.0, True, root=root,
                       allow_cpu=True)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW_METRICS) <= set(got), sorted(got)
    # whole 32-row tiles hold a few rows of each of 8 held experts
    assert got["expert_rows_per_live"] > 1.0
    # the busiest of 8 held experts carries at least the mean, at most
    # all of a layer's rows
    assert 1.0 <= got["expert_load_max_per_mean"] <= 8.0
    # every lane's whole page table is read; only held positions are live
    assert got["latent_rows_read_per_live"] > 1.0
    # and every per-layer metric the benchmark lists for the cell
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if CELL in m.get("workloads", [CELL])}
    assert listed <= set(got), sorted(listed - set(got))
