"""Model FLOPs per second of the window over the chips' bf16 peak, in
percent: `tokens_per_s` x 6N + 12 L (H hd) S FLOPs a token
(`bench/train_work.py`) / (chips x peak, `bench/peaks.py`), host clock."""
from bench import peaks, train_work
from bench.harness import load_module


def read(run):
    rate = load_module("metrics", "tokens_per_s").read(run)
    if rate is None:
        return None
    cell = run.cell
    flops = rate * train_work.flops_per_token(cell["config"]["model"],
                                              cell["traffic"]["seq"])
    return 100.0 * flops / (train_work.chips(cell) * peaks.peak(
        peaks.device_kind(), "bf16_flops"))
