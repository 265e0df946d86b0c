"""Test-size cells, added to a copy of the benchmark as new files and
entries, the way a later change adds a cell."""
import json
import os
import shutil

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# test-size cells: the trainer's storage (bf16 weights) and the structure
# of the two configurations, at widths a CPU run holds
TINY = {
    "tiny-gqa": dict(
        name="tiny-gqa", arch_type="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab=512, head_dim=32,
        norm="rms", act="silu", glu=True, qkv_bias=True, rope_frac=1.0,
        rope_theta=1000000.0, tie_embeddings=True, dtype="bfloat16"),
    "tiny-mha": dict(
        name="tiny-mha", arch_type="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab=512, head_dim=32,
        norm="layer", act="silu", glu=True, qkv_bias=True, rope_frac=0.25,
        rope_theta=10000.0, tie_embeddings=False, dtype="bfloat16"),
}
OPTIMIZER = {"lr": 1e-3, "warmup": 20, "total_steps": 1000,
             "final_frac": 0.05, "weight_decay": 0.01, "b1": 0.9,
             "b2": 0.999, "eps": 1e-8, "clip_norm": 1.0}
# Limits of the test-size cells, set from CPU readings at these sizes over
# seeds 1-6 (program, largest) and 1-3 (fp8 control and faults, smallest);
# see test_bench_faults.py.
TINY_LIMITS = {"loss_gap": {"limit": 3e-4}, "grad_gap": {"limit": 3e-3},
               "change_gap": {"limit": 0.3}}
CELLS = {
    "tiny-mha.1chip": ("tiny-mha", 1),
    "tiny-gqa.1chip": ("tiny-gqa", 1),
    "tiny-gqa.dp4": ("tiny-gqa", 4),
}


def add_tiny_cells(root):
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``root`` and add the
    test-size cells there as new files and new entries only."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "bench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, model in TINY.items():
        with open(os.path.join(b, "configs", f"{name}.json"), "w") as f:
            json.dump({"reference": "dense", "model": model,
                       "source": "test size", "reduced": {}, "assumed": {}},
                      f)
        bench["configs"].append({"name": name, "source": "test size",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test size"})
    for chips in (1, 4):
        # the reference spreads each block's rows over the cell's chips
        with open(os.path.join(b, "traffic", f"tiny.b2x64.c{chips}.json"),
                  "w") as f:
            json.dump({"batch_per_chip": 2, "seq_len": 64,
                       "vocab_slice": 4096, "branching": 8, "jump": 0.05,
                       "pool": 4, "strategy": "auto", "optimizer": OPTIMIZER,
                       "checked_steps": 3, "trace_steps": 3,
                       "reference_rows_per_block": chips}, f)
    for cell, (config, chips) in CELLS.items():
        with open(os.path.join(b, "limits", f"{cell}.json"), "w") as f:
            json.dump(TINY_LIMITS, f)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": f"tiny.b2x64.c{chips}",
                                   "chips": chips,
                                   "why": "test size"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
