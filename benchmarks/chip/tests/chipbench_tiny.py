"""Small sizes at which the tests drive the benchmark on the CPU: the
configurations' widths shrunk, the mixes' lengths shortened, nothing
else changed."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TRAIN_TRAFFIC = {"seq": 128, "batch": 2, "seqs_per_client": 32}

GRANITE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 32, "num_local_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 512,
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "vocab": 512, "d_ff": 32, "dtype": "bfloat16",
              "moe": {"n_experts": 4, "top_k": 2, "d_ff": 32,
                      "capacity_factor": 2.0}},
}

# Limits at this size, set as the cell's own are (PERF.md) from readings
# at this size over seeds 3,000,000,019, 11, 2**33 + 1 and 5: sound runs
# read a first-round loss gap of at most 0.00036, a median leaf's update
# difference of 0.018 and ν difference of 0.0345; the float8 control at
# least 0.00063, 0.074 and 0.123; the half batch 0.0041, 0.188 and 0.384.
TRAIN_LIMITS = {"loss_gap_r0": 0.0004, "update_diff_median": 0.042,
                "nu_diff_median": 0.074}

TINY = {
    "granite-fedagrac-kasync": {"config": GRANITE, "traffic": TRAIN_TRAFFIC,
                                "limits": TRAIN_LIMITS},
}
