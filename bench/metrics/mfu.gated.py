"""6·N·tokens/s over the chip's bf16 peak (``bench.counts.mamba2``).
The GP scoring pass is not model work, so it shows as a lower share."""


def read(trace, counts):
    rate = counts["tokens"] / trace["window_s"]
    return 100.0 * counts["flops_per_token"] * rate \
        / counts["peak"]["bf16_flops_per_s"]
