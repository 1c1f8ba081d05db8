"""Model FLOP/s of the FL rounds over the chip's bf16 peak: per round the
cohort's local training plus the global model's evaluation
(``bench.counts.small_models.flops_per_round``), times rounds per second
of the traced window."""


def read(trace, counts):
    rate = counts["rounds"] / trace["window_s"]
    return 100.0 * counts["flops_per_round"] * rate \
        / counts["peak"]["bf16_flops_per_s"]
