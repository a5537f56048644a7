"""Model FLOP/s utilization: operations the forward and backward passes need
for a token (stats.train_flops_per_step: attention in full, recomputation not
counted) times tokens per second, over chips times the bf16 peak."""
from benchmarks.harness import stats


def read(ctx):
    job = ctx["cell"]["traffic_file"]["job"]
    flops = stats.train_flops_per_step(ctx["cell"]["config_file"]["config"], job["batch"], job["seq"])
    per_token = flops / (job["batch"] * job["seq"])
    return stats.mfu_percent(per_token, ctx["train_tok_s"], ctx["chips"], ctx["device"]["kind"])
