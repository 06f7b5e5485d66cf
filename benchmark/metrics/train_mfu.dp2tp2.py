"""Model FLOPs of the traced window's training tokens over the window, as a
share of the bf16 peak of all the cell's chips: benchmark.flops
.train_flops_per_token x tokens/s / (chips x one chip's peak).  Remat is
not counted, nor any communication."""

from benchmark import peaks


def read(run):
    r = run["records"]
    if "train_tokens_per_s" not in r or "chips" not in r:
        return None
    return (100.0 * r["flops_per_token"] * r["train_tokens_per_s"]
            / (r["chips"] * peaks.flops_per_s(run["device_kind"])))
