"""Model FLOP/s utilization of the rounds, in %: the forward and
backward operations one sample requires (gate and its top-k experts),
times the samples per second, over the bf16 peak of the cell's chips."""
import costs


def read(w):
    if not w.rounds:
        return None
    rate = w.batch * w.rounds / w.seconds
    return (100.0 * costs.round_sample_flops(w.c) * rate
            / (w.chips * w.peak["bf16_flops_s"]))
