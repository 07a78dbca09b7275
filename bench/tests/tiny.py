"""Tiny cells that exist only in a test's own checkout: a Llama-layout LM
and a six-table DLRM, their mixes, and a per-layer metric of their own."""
import json
from pathlib import Path

LM_CFG = {
    "kind": "lm", "name": "test-lm", "source": "test-owned tiny Llama layout",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "dtype": "float32",
    "engine": {"batch_slots": 4, "max_len": 64, "prefill_buckets": [16, 32],
               "attention_impl": "chunked_jnp", "precision": "fp32"},
    "chips": 1, "replicas": 1,
    "check": {"requests": 4, "tokens": 40, "seq_len": 48, "positions": 16,
              "gap_limit": 1e-3}}
LM_MIX = {"loop": "open", "arrivals": {"process": "gamma", "rate_per_s": 6.0,
                                       "cv": 2.0},
          "prompt_tokens": {"dist": "uniform", "min": 4, "max": 22},
          "prefix": {"groups": 2, "tokens": {"dist": "fixed", "value": 8}},
          "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 2, "max": 12}}
DLRM_CFG = {
    "kind": "dlrm", "name": "test-dlrm", "source": "test-owned tiny DLRM",
    "num_dense_features": 13, "embedding_dim": 16,
    "table_rows": [100 + 10 * i for i in range(6)],
    "avg_lookups_per_table": [1 + i % 4 for i in range(6)],
    "max_lookups_per_table": 8, "bottom_mlp": [32, 16],
    "top_mlp": [64, 32, 1], "table_block_rows": 64,
    "engine": {"partial_transfers": True, "step_group": 4,
               "matmul_precision": "highest"},
    "chips": 1, "check": {"requests": 3, "error_limit": 1e-4}}
DLRM_MIX = {"loop": "closed", "callers": 4, "items_per_request": 8,
            "bags": {"dist": "poisson_plus_one"},
            "ids": {"dist": "uniform"}}
DLRM_OPEN_MIX = {"loop": "open",
                 "arrivals": {"process": "poisson", "rate_per_s": 8.0},
                 "items_per_request": 8,
                 "bags": {"dist": "poisson_plus_one"},
                 "ids": {"dist": "zipf", "alpha": 1.1}}

# a per-layer metric that only the test's checkout holds
METRIC = '''
def read(run):
    return float(run.result["e2e"]["attempted"])
'''


def checkout(tmp: Path, cells) -> Path:
    """A checkout whose configurations, mixes and one metric exist only
    here: ``cells`` is [(cell, config dict, mix dict, chips)]."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "metrics").mkdir()
    (tmp / "bench" / "metrics" / "test.requests_due.py").write_text(METRIC)
    configs, workloads = [], []
    for name, cfg, mix, chips in cells:
        f = f"bench/configs/{cfg['name']}.json"
        (tmp / f).write_text(json.dumps(cfg))
        (tmp / "bench" / "traffic" / f"{name}-mix.json").write_text(
            json.dumps(mix))
        if cfg["name"] not in {c["name"] for c in configs}:
            configs.append({"name": cfg["name"], "source": "test", "file": f,
                            "reduced": [], "why": "test"})
        workloads.append({"name": name, "config": cfg["name"],
                          "traffic": f"{name}-mix", "chips": chips,
                          "why": "test"})
    lm = [w["name"] for w, c in zip(workloads, cells) if c[1]["kind"] == "lm"]
    dl = [w["name"] for w, c in zip(workloads, cells)
          if c[1]["kind"] == "dlrm"]
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2, "configs": configs, "workloads": workloads,
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock", "workloads": lm},
            {"name": "rank_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock", "workloads": dl},
            {"name": "completed_rps", "unit": "req/s", "better": "higher",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "test.requests_due", "unit": "req", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "completed_rps"},
            {"name": "lm.queue_wait_p95_ms", "unit": "ms", "better": "lower",
             "source": "program_counter", "layer": "scheduler",
             "moves": "ttft_p95_ms", "workloads": lm},
            {"name": "dlrm.window_compiles", "unit": "compiles/req",
             "better": "lower", "source": "program_counter",
             "layer": "ingest", "moves": "rank_p95_ms", "workloads": dl}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
